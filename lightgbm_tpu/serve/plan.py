"""PredictPlan: a Booster slice frozen into a cached, device-resident
inference program.

Training-side prediction (``GBDT._predict_raw_own``) re-runs host binning
and re-builds the SoA tree pack on EVERY call; the reference instead keeps
a long-lived ``Predictor`` with pre-extracted traversal state
(``src/application/predictor.cpp``), and the GPU-boosting literature
(arXiv:1706.08359, arXiv:1806.11248) is blunt that batched device
traversal only pays off once the model stays resident and dispatch
overhead is amortized.  A PredictPlan is that resident state for the TPU
build:

- the ``(T, ...)`` stacked tree arrays per class (built ONCE from the host
  mirrors, uploaded once),
- the binning tables (bound sort keys, categorical vocabularies,
  NaN / zero-as-missing routing — serve/device_binning.py),
- two jitted programs: raw f64 bits -> bins -> per-class scores, and
  pre-binned rows -> scores (the sparse-input path),
- shape bucketing + compile accounting.

Plans are cached per ``(model identity, iteration slice, model version)``
so repeated predicts never re-stack or re-upload; the cache keeps hit /
miss / build / eviction counters (assertable from tests and exported by
the serving metrics).
"""

from __future__ import annotations

import hashlib
import threading
import time
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.tree import (forest_scores, forest_scores_quantized,
                           quantize_error_bound, quantize_stack_trees,
                           stack_trees)
from ..utils.log import Log
from .bucketing import BucketLadder
from .device_binning import bin_rows_device, build_bin_tables, float_bits


class PredictPlan:
    """Frozen, device-resident predict state for one Booster slice."""

    def __init__(self, model, start_iteration: int, end_iteration: int,
                 ladder: Optional[BucketLadder] = None,
                 quantize: Optional[str] = None,
                 traverse: Optional[str] = None,
                 compile_cache: Optional[str] = None):
        binned = model.train_data.binned
        self._model_ref = weakref.ref(model)
        self.start_iteration = int(start_iteration)
        self.end_iteration = int(end_iteration)
        self.num_class = int(model.num_class)
        self.num_features = int(binned.num_features)
        self.init_scores = np.asarray(model.init_scores, np.float64).copy()
        self.ladder = ladder or BucketLadder()
        tables = build_bin_tables(binned.mappers)
        if tables is None:
            raise ValueError("device binning unavailable for this dataset")
        self._tables = tables
        # ONE batched host transfer for ONLY the sliced iterations
        # (host_trees materializes lazily per range), then one stack+upload
        # per class — the only time this plan touches the host mirrors.
        trees_by_class = model.host_trees(self.start_iteration,
                                          self.end_iteration)
        self.num_trees = sum(len(t) for t in trees_by_class)
        self._nan_bins = jnp.asarray(binned.nan_bins, jnp.int32)
        # Quantized serving packs (ISSUE-12, docs/SERVING.md): int16/int8
        # leaf quanta + narrow node arrays + bit-packed cat masks — ~4x
        # smaller resident footprint with exact routing; leaf values
        # round within quantize_error_bound().  Shapes the narrow
        # encodings can't hold degrade to the fp32 pack with a warning.
        self._stacked = None
        self._packs = None
        self.quantize_mode = "off"
        quantize = _resolve_quantize(model, quantize, warn=True)
        if quantize != "off":
            packs = [quantize_stack_trees(trees, model.cfg.num_leaves,
                                          binned.max_num_bins, quantize)
                     if trees else None for trees in trees_by_class]
            if any(p is None and trees
                   for p, trees in zip(packs, trees_by_class)):
                Log.warning(
                    f"serve: tpu_serve_quantize={quantize} needs "
                    "num_leaves/bins/features <= 32767; falling back to "
                    "the fp32 pack")
            else:
                # the dequant scale is VALUE-derived (max|leaf|): carry it
                # as a 0-d device operand so a refit/retrain swap (same
                # structure, new values) keeps the structural identity —
                # and the zero-cold-start executables — intact
                self._packs = [None if p is None
                               else dict(p, scale=jnp.float32(p["scale"]))
                               for p in packs]
                self.quantize_mode = quantize
        if self._packs is None:
            self._stacked = [
                stack_trees(trees, model.cfg.num_leaves,
                            binned.max_num_bins)
                if trees else None
                for trees in trees_by_class]
        self.traverse_mode, self.traverse_degrade = _resolve_traverse(
            model, traverse, self.quantize_mode, self._packs,
            self.num_features)
        from ..ops.pallas_common import interpret_mode
        self._interpret = interpret_mode()
        self.stack_count = 1          # re-stacks would increment (never do)
        # Resident bytes for this plan (tree pack — quantized or fp32 —
        # + bin tables + NaN routing) — the per-plan half of the serve
        # byte accounting (docs/SERVING.md): plan-cache admission/eviction
        # by bytes (ROADMAP item 1) consumes exactly this number.
        # ``pack_bytes`` is the tree pack alone: the part quantization
        # shrinks (the bin tables are f64-exactness-bound and shared by
        # every mode), so shrink ratios stay meaningful on small models
        # where the tables dominate.
        self.pack_bytes = _pytree_bytes(
            self._packs if self._packs is not None else self._stacked)
        self.plan_bytes = self.pack_bytes + _pytree_bytes(
            (self._tables, self._nan_bins))

        # The pack/table arrays ride as jit ARGUMENTS (one device-resident
        # pytree), not closure constants: the compiled executables then
        # depend only on SHAPES/dtypes/modes, so a hot-swapped model
        # version (same architecture, new values) reuses the previous
        # version's executables — in-process jit cache AND the persistent
        # AOT cache (structural ``identity``) — paying ZERO cold-start
        # compiles on swap (docs/STREAMING.md serve handoff).
        self._arrays, self._static = _partition_arrays(
            ((self._packs if self._packs is not None else self._stacked),
             self._tables, self._nan_bins))
        quantized = self._packs is not None
        fused = self.traverse_mode == "fused"
        interp = self._interpret
        static = self._static

        def _scores(arrs, bins):
            packs, _tables, nan_bins = _merge_arrays(arrs, static)
            if quantized:
                return forest_scores_quantized(
                    packs, bins, nan_bins, fused=fused, interpret=interp)
            return forest_scores(packs, bins, nan_bins)

        def _from_bits(arrs, hi, lo):
            _packs, tables, _nb = _merge_arrays(arrs, static)
            return _scores(arrs, bin_rows_device(tables, hi, lo))

        # watch_compiles (telemetry/spans.py): each new ladder rung's XLA
        # compile lands as a compile.end event; launches already run
        # under the predictor's serve/predict span.
        from ..telemetry import watch_compiles
        self._jit_bits = jax.jit(_from_bits)
        self._jit_binned = jax.jit(_scores)
        self._predict_bits = watch_compiles(self._jit_bits,
                                            "serve/predict_bits")
        self._predict_binned = watch_compiles(self._jit_binned,
                                              "serve/predict_binned")
        self._shapes = set()          # padded (kind, rows) this plan compiled
        self._lock = threading.Lock()
        # Persistent AOT compile cache (serve/compile_cache.py): compiled
        # executables for this plan's ladder rungs round-trip through disk
        # so a restart/hot-swap pays ZERO XLA compiles on warm entries.
        self._aot: Dict[tuple, object] = {}
        self.aot_hits = 0
        self.aot_compiles = 0
        self._ccache = None
        self._identity = None
        if compile_cache is None:
            from .compile_cache import cache_dir_for
            compile_cache = cache_dir_for(model.cfg)
        if compile_cache:
            from .compile_cache import CompileCache
            self._ccache = CompileCache(compile_cache)
        # model mutation state at build time (iter_, num_trees,
        # _pred_version): the Predictor's per-request freshness check
        # compares the live model against this to hot-swap stale plans
        self.built_state = (int(model.iter_), int(model.num_trees),
                            int(getattr(model, "_pred_version", 0)))

    # ------------------------------------------------------------- identity
    @property
    def identity(self) -> str:
        """STRUCTURAL digest of everything the compiled predict programs
        bake in — shapes/dtypes of every pack/table leaf plus the modes
        and static metadata; array VALUES are runtime arguments and
        deliberately not hashed.  That makes the AOT cache key shared
        across model VERSIONS of the same architecture: a retrain/refit
        hot-swap loads the previous version's executables from disk
        (zero cold-start), while a re-slice, shape change, mode change or
        jax upgrade still forks the key.  Safe because the executables
        carry no model values — every call passes the plan's own
        resident arrays."""
        if self._identity is None:
            h = hashlib.sha256()
            h.update(f"{self.num_class}|{self.num_features}|"
                     f"{self.quantize_mode}|{self.traverse_mode}|"
                     f"{self._interpret}".encode())
            for leaf in jax.tree_util.tree_leaves(self._arrays):
                h.update(f"{tuple(leaf.shape)}|{leaf.dtype}".encode())
            # static metadata (quantized scale excluded by partition? no:
            # non-array leaves — scale/bits/depth — ARE baked into the
            # trace, so they stay in the digest)
            h.update(repr(jax.tree_util.tree_leaves(
                self._static, is_leaf=lambda x: not isinstance(
                    x, (dict, list, tuple)))).encode())
            self._identity = h.hexdigest()
        return self._identity

    def quantize_error_bound(self) -> float:
        """Worst-case |quantized - fp32| raw-score gap (max across
        classes; 0.0 for fp32 packs) — the fp32-parity harness's pinned
        tolerance (tests/test_serve_quantize.py)."""
        if self._packs is None:
            return 0.0
        # scale rides as a 0-d device operand (structural identity);
        # the bound is host-facing — pin it back to a float
        return max((float(quantize_error_bound(p)) for p in self._packs
                    if p is not None), default=0.0)

    # ---------------------------------------------------------- AOT dispatch
    def _call(self, kind: str, *args):
        """Launch one predict program: straight through the jitted entry
        when no compile cache is configured (today's path), else through
        the per-rung AOT executable — loaded from disk when a prior
        process compiled it (zero cold-start), compiled-and-persisted
        otherwise."""
        if self._ccache is None:
            fn = (self._predict_bits if kind == "bits"
                  else self._predict_binned)
            return fn(self._arrays, *args)
        key = (kind, int(args[0].shape[0]))
        with self._lock:
            compiled = self._aot.get(key)
        if compiled is None:
            compiled = self._aot_compile(kind, key, args)
        return compiled(self._arrays, *args)

    def _aot_compile(self, kind: str, key: tuple, args):
        from .compile_cache import entry_key
        ck = entry_key(self.identity, kind, key[1])
        home = sorted(jax.tree.leaves(self._arrays)[0].devices(),
                      key=lambda d: d.id)
        compiled = self._ccache.load(ck, devices=home)
        fresh = compiled is None
        if fresh:
            jit_fn = self._jit_bits if kind == "bits" else self._jit_binned
            t0 = time.perf_counter()
            compiled = jit_fn.lower(self._arrays, *args).compile()
            # compile telemetry (the jit seam can't see AOT compiles):
            # every fresh rung compile lands as a compile.end event with
            # its memory_analysis byte summary, mirroring profile_iter.
            from ..telemetry.memory import note_compile
            note_compile(f"serve/aot_{kind}", time.perf_counter() - t0,
                         compiled=compiled)
            self._ccache.store(ck, compiled)
        with self._lock:
            self._aot[key] = compiled
            if fresh:
                self.aot_compiles += 1
            else:
                self.aot_hits += 1
        return compiled

    def aot_stats(self) -> Optional[Dict[str, int]]:
        """Zero-cold-start counters: this plan's disk hits vs fresh
        compiles, plus the cache-level frame counters (None when no cache
        is configured) — ``BENCH_serve``'s post-restart compile count
        reads exactly this."""
        if self._ccache is None:
            return None
        with self._lock:
            out = {"hits": self.aot_hits, "compiles": self.aot_compiles}
        out["cache"] = self._ccache.stats()
        return out

    # ------------------------------------------------------------ accounting
    def compile_count(self) -> int:
        """Distinct FRESH XLA compiles behind this plan: the jit
        executable-cache sizes plus AOT compiles this process actually
        paid (disk-loaded executables are deliberately NOT counted — they
        are the compiles a restart skipped, reported via aot_stats()).
        Falls back to the padded-shape census on a jax without
        ``_cache_size``."""
        n = self.aot_compiles
        for fn in (self._jit_bits, self._jit_binned):
            try:
                n += int(fn._cache_size())
            except Exception:  # noqa: BLE001 — older jax: census fallback
                with self._lock:
                    return len(self._shapes)
        return n

    def _note_shape(self, kind: str, padded: int) -> None:
        with self._lock:
            self._shapes.add((kind, padded))

    def is_for(self, model) -> bool:
        return self._model_ref() is model

    @property
    def tenant(self) -> Optional[str]:
        """Model label for per-tenant attribution (ISSUE-14): the serve
        label a named ``Predictor`` stamped on the model, read LIVE so a
        cached plan follows a late naming.  ``None`` for unnamed models
        (their bytes attribute to the ``_unnamed`` bucket)."""
        model = self._model_ref()
        return None if model is None else getattr(model, "_serve_label",
                                                  None)

    # ------------------------------------------------------------ prediction
    def _pad(self, arrs, n: int):
        padded = self.ladder.bucket(n)
        if padded == n:
            return arrs, padded
        return [np.pad(a, ((0, padded - n), (0, 0))) for a in arrs], padded

    def raw_scores(self, X, metrics=None, trace=None) -> np.ndarray:
        """(N, K) f64 raw scores (init scores included) for dense rows —
        host work is one bit-split view + ladder pad; binning, traversal
        and per-class accumulation run as ONE jitted dispatch.  ``trace``
        (a ``serve.metrics.PhaseTrace``) marks the assemble/dispatch
        boundary split — host ``perf_counter`` arithmetic only, the
        compiled program is identical with or without it (ISSUE-14
        inertness pin)."""
        X = np.asarray(X)
        n = X.shape[0]
        if X.ndim != 2 or X.shape[1] != self.num_features:
            raise ValueError(
                f"plan expects (N, {self.num_features}) rows, got {X.shape}")
        if n == 0:
            return np.zeros((0, self.num_class), np.float64) \
                + self.init_scores[None, :]
        hi, lo = float_bits(X)
        (hi, lo), padded = self._pad([hi, lo], n)
        self._note_shape("bits", padded)
        if trace is not None:
            trace.mark("assemble")
        scores = self._call("bits", jnp.asarray(hi), jnp.asarray(lo))
        if metrics is not None:
            metrics.observe_batch(n, padded)
        out = np.asarray(jax.device_get(scores), np.float64)[:n]
        if trace is not None:       # upload + launch + blocking fetch
            trace.mark("dispatch")
        out += self.init_scores[None, :]
        return out

    def raw_scores_binned(self, bins: np.ndarray, metrics=None,
                          trace=None) -> np.ndarray:
        """(N, K) f64 raw scores from PRE-BINNED rows (the sparse-input
        path: host binning straight from CSC, device traversal from the
        resident pack — still no re-stacking)."""
        bins = np.asarray(bins)
        n = bins.shape[0]
        if n == 0:
            return np.zeros((0, self.num_class), np.float64) \
                + self.init_scores[None, :]
        (bins,), padded = self._pad([bins], n)
        self._note_shape("binned", padded)
        if trace is not None:
            trace.mark("assemble")
        scores = self._call("binned", jnp.asarray(bins))
        if metrics is not None:
            metrics.observe_batch(n, padded)
        out = np.asarray(jax.device_get(scores), np.float64)[:n]
        if trace is not None:
            trace.mark("dispatch")
        out += self.init_scores[None, :]
        return out

    def warmup(self, max_rows: int) -> int:
        """Pre-compile the dense-path program for every ladder rung up to
        ``bucket(max_rows)``; returns the number of rungs warmed."""
        rungs = self.ladder.rungs_upto(max_rows)
        for m in rungs:
            self.raw_scores(np.zeros((m, self.num_features)))
        return len(rungs)


class _ArraySlot:
    """Sentinel marking 'this leaf lives in the arrays pytree'."""

    __slots__ = ()

    def __repr__(self):
        return "<array>"


_ARRAY = _ArraySlot()


def _partition_arrays(obj):
    """Split a nested pack/table structure into (device arrays pytree,
    static skeleton).  Arrays become jit ARGUMENTS (uploaded once here);
    ints/floats/strings stay trace-time constants.  ``_merge_arrays``
    reassembles the original structure inside the trace."""
    if isinstance(obj, dict):
        arrs, stat = {}, {}
        for k, v in obj.items():
            arrs[k], stat[k] = _partition_arrays(v)
        return arrs, stat
    if isinstance(obj, (list, tuple)):
        pairs = [_partition_arrays(v) for v in obj]
        return (type(obj)(p[0] for p in pairs),
                type(obj)(p[1] for p in pairs))
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):
        return jnp.asarray(obj), _ARRAY
    return None, obj


def _merge_arrays(arrs, stat):
    if stat is _ARRAY:
        return arrs
    if isinstance(stat, dict):
        return {k: _merge_arrays(arrs[k], stat[k]) for k in stat}
    if isinstance(stat, (list, tuple)):
        return type(stat)(_merge_arrays(a, s) for a, s in zip(arrs, stat))
    return stat


def _pytree_bytes(tree) -> int:
    """Total array bytes across a pytree (stacked packs, table dicts)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def _resolve_quantize(model, quantize: Optional[str],
                      warn: bool = False) -> str:
    """Effective pack quantize mode: the explicit kwarg wins, else the
    booster's ``tpu_serve_quantize`` knob; unknown spellings mean off
    (warned only from the plan BUILD — this also runs in the hot-path
    cache-key computation, which must not spam the log)."""
    if quantize is None:
        quantize = getattr(model.cfg, "tpu_serve_quantize", "off")
    quantize = str(quantize).lower()
    if quantize not in ("off", "int16", "int8"):
        if warn:
            Log.warning(f"serve: unknown tpu_serve_quantize={quantize!r} "
                        "(expected off|int16|int8); using off")
        return "off"
    return quantize


def _resolve_traverse(model, traverse: Optional[str], quantize_mode: str,
                      packs, num_features: int):
    """(mode, degrade_reason) for the traversal kernel.  fused needs a
    quantized pack (integer identity is the kernel's contract) and the
    VMEM fit gate; auto additionally requires a live TPU backend (on CPU
    the kernel only runs in interpret mode — a test vehicle, engaged by
    forcing fused, never by auto)."""
    if traverse is None:
        traverse = getattr(model.cfg, "tpu_traverse_kernel", "auto")
    traverse = str(traverse).lower()
    if traverse not in ("auto", "fused", "unfused"):
        Log.warning(f"serve: unknown tpu_traverse_kernel={traverse!r} "
                    "(expected auto|fused|unfused); using unfused")
        return "unfused", f"unknown mode {traverse!r}"
    if traverse == "unfused":
        return "unfused", None
    if quantize_mode == "off" or packs is None:
        reason = "fused traversal needs a quantized pack " \
                 "(tpu_serve_quantize=int16|int8)"
        if traverse == "fused":
            Log.warning(f"serve: tpu_traverse_kernel=fused ignored — "
                        f"{reason}")
            return "unfused", reason
        return "unfused", None          # auto simply doesn't engage
    from ..ops.pallas_traverse import traverse_layout_fits
    fits = all(
        traverse_layout_fits(int(p["leaf_q"].shape[0]),
                             int(p["leaf_q"].shape[1]), num_features,
                             int(p["num_bins"]))
        for p in packs if p is not None)
    if not fits:
        reason = "tree pack exceeds the traversal kernel's VMEM budget"
        if traverse == "fused":
            Log.warning(f"serve: tpu_traverse_kernel=fused ignored — "
                        f"{reason}")
        return "unfused", reason
    if traverse == "auto" and jax.default_backend() != "tpu":
        return "unfused", None
    return "fused", None


# ---------------------------------------------------------------- plan cache
_CACHE: "OrderedDict[tuple, PredictPlan]" = OrderedDict()
_CACHE_LOCK = threading.Lock()
_CACHE_CAP = 8
_STATS = {"hits": 0, "misses": 0, "builds": 0, "evictions": 0}
# Per-key in-flight build markers: N threads missing the same key must run
# ONE stack+upload, not N (the losers wait on the winner's Event).
_INFLIGHT: Dict[tuple, threading.Event] = {}


def _stale_locked(key, plan) -> bool:
    """A cache entry is stale when its model was garbage-collected or has
    trained/rolled past the keyed (iter_, num_trees) state — the key can
    never hit again, but the entry would pin a device-resident tree pack
    until cap pressure evicted it."""
    model = plan._model_ref()
    if model is None:
        return True
    return (int(model.iter_), int(model.num_trees),
            int(getattr(model, "_pred_version", 0))) != key[3:6]


def _sweep_dead_locked() -> int:
    """Drop stale entries (caller holds _CACHE_LOCK); returns how many
    were removed, so hit-path callers republish the byte gauges only
    when something actually changed."""
    stale = [k for k, p in _CACHE.items() if _stale_locked(k, p)]
    for k in stale:
        del _CACHE[k]
        _STATS["evictions"] += 1
    return len(stale)


def _resolve_slice(model, num_iteration: Optional[int],
                   start_iteration: int):
    # dev_models (not the .models property): a cache HIT must not touch —
    # let alone materialize — the host tree mirrors.
    n = len(model.dev_models[0]) if model.dev_models else 0
    start = max(int(start_iteration), 0)
    end = n if num_iteration is None else min(n, start + int(num_iteration))
    return start, max(end, start)


def plan_for_model(model, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   ladder: Optional[BucketLadder] = None,
                   quantize: Optional[str] = None,
                   traverse: Optional[str] = None,
                   compile_cache: Optional[str] = None
                   ) -> Optional[PredictPlan]:
    """Fetch (or build) the cached PredictPlan for a GBDT slice.

    The key carries the model's identity AND its mutation state (``iter_``,
    ``num_trees``, ``_pred_version`` — the latter bumped by in-place leaf
    mutations like the C-API's SetLeafValue/Refit): training another
    round, rolling one back, or rewriting leaves changes the key, so a
    stale pack can never serve.  ``quantize``/``traverse``/
    ``compile_cache`` override the booster's knobs per plan (per-tenant
    pack formats, ROADMAP item 1) and ride the key — a quantized plan and
    the fp32 plan of the same model coexist in the cache.  Returns None
    when the dataset cannot be device-binned exactly (callers fall back
    to the legacy host path); that verdict is dataset-level and
    permanent, so it is memoized on the model — the hot predict path must
    not re-derive the bin tables just to fail again."""
    if getattr(model, "_serve_unsupported", False):
        return None
    ladder = ladder or BucketLadder()
    start, end = _resolve_slice(model, num_iteration, start_iteration)
    # Key on NORMALIZED mode requests (kwarg-or-knob, lowercased; cache
    # dir through the env/knob resolution): Predictor(bst) and
    # Predictor(bst, traverse="auto") describe the same plan and must
    # share one device-resident build, not double the cache bytes.
    if traverse is None:
        traverse = getattr(model.cfg, "tpu_traverse_kernel", "auto")
    traverse = str(traverse).lower()
    if compile_cache is None:
        from .compile_cache import cache_dir_for
        compile_cache = cache_dir_for(model.cfg)
    key = (id(model), start, end, int(model.iter_), int(model.num_trees),
           int(getattr(model, "_pred_version", 0)), ladder,
           _resolve_quantize(model, quantize), traverse, compile_cache)
    while True:
        with _CACHE_LOCK:
            plan = _CACHE.get(key)
            # id() can be recycled after GC — the weakref check makes a
            # hit structural, not just numeric.
            if plan is not None and plan.is_for(model):
                _STATS["hits"] += 1
                _CACHE.move_to_end(key)
                # sweep on hits too: a steady stream of cache hits must
                # not pin dead models' tree packs until the next build —
                # and the byte gauges must follow an actual eviction, or
                # a scraper sees evicted packs' bytes forever.  A clean
                # hit (the common case) publishes nothing: the serve hot
                # path pays no registry work and no O(cache) byte sum.
                if _sweep_dead_locked():
                    _publish_bytes_locked()
                return plan
            ev = _INFLIGHT.get(key)
            if ev is None:
                _INFLIGHT[key] = threading.Event()
                _STATS["misses"] += 1
                break
        # Another thread is stacking/uploading this exact plan — wait for
        # it, then re-check (if it failed, the loop makes us the builder).
        ev.wait()
    plan = None
    try:
        plan = PredictPlan(model, start, end, ladder=ladder,
                           quantize=quantize, traverse=traverse,
                           compile_cache=compile_cache)
    except ValueError:
        model._serve_unsupported = True
        return None
    finally:
        with _CACHE_LOCK:
            if plan is not None:
                _STATS["builds"] += 1
                _CACHE[key] = plan
                _CACHE.move_to_end(key)
                _sweep_dead_locked()
                while len(_CACHE) > _CACHE_CAP:
                    _CACHE.popitem(last=False)
                    _STATS["evictions"] += 1
            _publish_bytes_locked()
            _INFLIGHT.pop(key).set()
    return plan


def _cache_bytes_locked() -> int:
    return sum(p.plan_bytes for p in _CACHE.values())


def _cache_bytes_by_tenant_locked() -> Dict[str, int]:
    """Resident plan-cache bytes grouped by model label (``_unnamed``
    for label-less models) — ROADMAP item 1's per-tenant admission input
    (a byte budget can only evict per tenant if the bytes attribute per
    tenant)."""
    out: Dict[str, int] = {}
    for p in _CACHE.values():
        name = p.tenant or "_unnamed"
        out[name] = out.get(name, 0) + p.plan_bytes
    return out


# tenant labels whose plan_cache_bytes gauge was ever published: an
# evicted tenant's gauge drops to 0 instead of lingering at its last value
_PUBLISHED_TENANTS: set = set()


def _publish_bytes_locked() -> None:
    """Byte gauges (docs/OBSERVABILITY.md serve section): the
    most-recently-used cached plan's resident bytes
    (``serve.plan_bytes``, 0 when the cache is empty — an evicted pack's
    bytes never linger in the gauge), the cache-wide total
    (``serve.plan_cache_bytes``) and the per-tenant labeled split
    (``serve.plan_cache_bytes{model="..."}``) — the admission-control
    input ROADMAP item 1's eviction-by-bytes will consume."""
    from ..telemetry import registry
    reg = registry()
    mru = next(reversed(_CACHE)) if _CACHE else None
    reg.gauge("serve.plan_bytes").set(
        _CACHE[mru].plan_bytes if mru is not None else 0)
    reg.gauge("serve.plan_cache_bytes").set(_cache_bytes_locked())
    by_tenant = _cache_bytes_by_tenant_locked()
    for name in _PUBLISHED_TENANTS - set(by_tenant):
        reg.gauge("serve.plan_cache_bytes",
                  labels={"model": name}).set(0)
    for name, nbytes in by_tenant.items():
        _PUBLISHED_TENANTS.add(name)
        reg.gauge("serve.plan_cache_bytes",
                  labels={"model": name}).set(nbytes)


def cache_stats() -> Dict[str, int]:
    """Hit/miss/build/eviction counters plus the live cache footprint:
    ``size`` (entries) AND ``bytes`` (resident device bytes across every
    cached plan — entry counts alone cannot drive byte-budget admission
    control, docs/SERVING.md), with labeled per-tenant
    ``bytes{model="..."}`` entries that render as labeled Prometheus
    series."""
    from ..telemetry.registry import labeled_name
    with _CACHE_LOCK:
        out = dict(_STATS, size=len(_CACHE), bytes=_cache_bytes_locked())
        for name, nbytes in _cache_bytes_by_tenant_locked().items():
            out[labeled_name("bytes", {"model": name})] = nbytes
    return out


def clear_plan_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()
        for k in ("hits", "misses", "builds", "evictions"):
            _STATS[k] = 0
        _publish_bytes_locked()
