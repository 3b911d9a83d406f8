"""Learner-composition capability matrix and the growth plan.

The reference composes tree learners orthogonally through virtual
dispatch (``tree_learner.cpp:31-44`` instantiates serial/feature/data/
voting × CPU/GPU/CUDA); this build instead specializes compiled layouts,
so some (learner × option) combinations downgrade to a safe layout or are
rejected.  Two steps, both HERE:

- ``resolve()`` adjudicates what a user can contradict in params alone
  (``RULES``: one declarative rule per downgrade or rejection), before the
  ``GrowerConfig`` is built;
- ``plan_growth()`` states, for a ``GrowerConfig`` on a mesh at a shape,
  the whole of what the grower then runs — body, layout, fused or not,
  histogram implementation, 4-bit bins, cross-shard reduction, pool,
  streamable or not — and the one sentence behind each refusal.
  ``GBDT.__init__`` keeps it as ``GBDT.plan``; ``make_grower`` and the
  bodies of ``models/grower.py`` route on it and decide nothing themselves.
"""

from __future__ import annotations

import dataclasses
from types import MappingProxyType
from typing import Callable, List, Mapping, Optional, Tuple

# Fewest rows (per shard) the permutation layout's smallest bucket holds;
# at or under it growth runs the mask body.
PERM_MIN_ROWS = 2048

HIST_IMPLS = ("auto", "pallas", "onehot", "segment")
WAVE_KERNELS = ("auto", "fused", "unfused")
HIST_COMMS = ("auto", "allreduce", "reduce_scatter")


@dataclasses.dataclass
class Composition:
    """The mutable facts ``resolve`` adjudicates.  ``voting``/
    ``leaf_batch``/``wave_kernel`` are the downgrade targets; everything
    else is read-only context."""

    voting: bool
    leaf_batch: int
    mono_method: str            # "none" | "basic" | "intermediate" | "advanced"
    forced_splits: bool
    extra_trees: bool
    feature_fraction_bynode: bool
    # "auto" | "fused" | "unfused" (tpu_wave_kernel).  Only an EXPLICIT
    # "fused" request fires the downgrade rules below — "auto" resolves
    # silently through plan_growth, which owns the full (dataset-fact-
    # dependent) decision; the rules here cover the composition axes a
    # user can contradict in params alone.
    wave_kernel: str = "auto"


def _mono_refresh(c: Composition) -> bool:
    # intermediate/advanced recompute bounds + best splits every step
    return c.mono_method in ("intermediate", "advanced")


def _fused_wave(c: Composition) -> bool:
    return c.wave_kernel == "fused"


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    applies: Callable[[Composition], bool]
    action: str                 # "error" | "fallback"
    message: str
    fix: Optional[Callable[[Composition], Composition]] = None


RULES: Tuple[Rule, ...] = (
    Rule("forced-x-wave",
         lambda c: c.forced_splits and c.leaf_batch > 1,
         "fallback",
         "forced splits require sequential leaf-wise growth; disabling "
         "wave batching (tpu_leaf_batch=1)",
         lambda c: dataclasses.replace(c, leaf_batch=1)),
    Rule("forced-x-voting",
         lambda c: c.forced_splits and c.voting,
         "fallback",
         "tree_learner=voting does not compose with forced splits; "
         "falling back to data-parallel",
         lambda c: dataclasses.replace(c, voting=False)),
    Rule("mono-refresh-x-voting",
         lambda c: _mono_refresh(c) and c.voting,
         "fallback",
         "tree_learner=voting does not compose with "
         "monotone_constraints_method=intermediate/advanced; falling back "
         "to data-parallel",
         lambda c: dataclasses.replace(c, voting=False)),
    Rule("mono-refresh-x-randomness",
         lambda c: _mono_refresh(c) and (c.extra_trees
                                         or c.feature_fraction_bynode),
         "error",
         "monotone_constraints_method=intermediate/advanced does not "
         "compose with extra_trees / feature_fraction_bynode; use "
         "monotone_constraints_method=basic"),
    Rule("mono-advanced-x-forced",
         lambda c: c.mono_method == "advanced" and c.forced_splits,
         "error",
         "monotone_constraints_method=advanced does not compose with "
         "forced_splits; use intermediate"),
    # ---- fused wave kernel (tpu_wave_kernel=fused, ops/pallas_wave.py).
    # The kernel scans both children inside one pallas_call, so anything
    # that changes the scan per NODE (monotone bounds, forced overwrites,
    # per-node randomness) or replaces the scan entirely (voting) keeps
    # the unfused wave path.
    Rule("fused-wave-x-forced",
         lambda c: _fused_wave(c) and c.forced_splits,
         "fallback",
         "tpu_wave_kernel=fused does not compose with forced splits "
         "(_apply_forced overwrites stored splits mid-growth); keeping "
         "the unfused wave path",
         lambda c: dataclasses.replace(c, wave_kernel="unfused")),
    Rule("fused-wave-x-monotone",
         lambda c: _fused_wave(c) and c.mono_method != "none",
         "fallback",
         "tpu_wave_kernel=fused does not compose with monotone "
         "constraints (the in-kernel scan carries no per-child output "
         "bounds); keeping the unfused wave path",
         lambda c: dataclasses.replace(c, wave_kernel="unfused")),
    Rule("fused-wave-x-randomness",
         lambda c: _fused_wave(c) and (c.extra_trees
                                       or c.feature_fraction_bynode),
         "fallback",
         "tpu_wave_kernel=fused does not compose with extra_trees / "
         "feature_fraction_bynode (per-node masks and thresholds); "
         "keeping the unfused wave path",
         lambda c: dataclasses.replace(c, wave_kernel="unfused")),
    Rule("fused-wave-x-voting",
         lambda c: _fused_wave(c) and c.voting,
         "fallback",
         "tpu_wave_kernel=fused does not compose with "
         "tree_learner=voting (voting scans compact vote-winner slices); "
         "keeping the unfused wave path",
         lambda c: dataclasses.replace(c, wave_kernel="unfused")),
)


def resolve(comp: Composition,
            warn: Optional[Callable[[str], None]] = None
            ) -> Tuple[Composition, List[Rule]]:
    """Apply every matching rule in order.  ``error`` rules raise
    ``ValueError(message)``; ``fallback`` rules rewrite the composition and
    report through ``warn``.  Returns the resolved composition plus the
    rules that fired (for tests/introspection)."""
    fired: List[Rule] = []
    for rule in RULES:
        if not rule.applies(comp):
            continue
        if rule.action == "error":
            raise ValueError(rule.message)
        comp = rule.fix(comp)
        fired.append(rule)
        if warn is not None:
            warn(rule.message)
    return comp, fired


@dataclasses.dataclass(frozen=True)
class GrowthPlan:
    """What one ``GrowerConfig`` on one mesh at one shape runs.

    ``why`` maps each thing the configuration asked for (by a parameter or
    by an ``auto`` default) and this plan refuses — ``"fused"``,
    ``"scatter"``, ``"pool"``, ``"feature"``, ``"packed4"``, ``"stream"``,
    ``"subset"`` — to the ONE sentence that refused it."""

    body: str                   # "mask" | "wave"
    layout: str                 # "single" | "data" | "feature" | "gspmd"
    fused: bool                 # the fused wave kernel (ops/pallas_wave.py)
    hist_impl: str              # "pallas" | "onehot" | "segment"
    packed4: bool               # bins travel as nibble pairs
    reduce: str                 # "none" | "psum" | "scatter" | "vote"
    pool: bool                  # bounded leaf-histogram pool
    stream_reason: Optional[str]  # None = the streamed trainer can run it
    why: Mapping[str, str]
    # the form a row sample takes: "none" (no row sampling), "subset" (the
    # tree is grown over the in-bag row ids alone) or "mask" (over every
    # row, the out-of-bag ones weighted 0)
    sampling: str = "none"

    def __str__(self):
        head = (f"body={self.body} layout={self.layout} fused={self.fused} "
                f"hist_impl={self.hist_impl} packed4={self.packed4} "
                f"reduce={self.reduce} pool={self.pool}")
        if self.sampling != "none":
            head += f" sampling={self.sampling}"
        return head + "".join(f"; no {k}: {v}" for k, v in self.why.items())


def _first(*pairs) -> Optional[str]:
    """The sentence of the first (condition, sentence) pair that holds."""
    return next((msg for hit, msg in pairs if hit), None)


def plan_growth(cfg, mesh, data_axis: str = "data", *,
                rows: Optional[int], features: Optional[int],
                platform: Optional[str] = None) -> GrowthPlan:
    """The growth plan of ``cfg`` (a ``GrowerConfig``) on ``mesh`` for
    ``rows`` x ``features`` of training data.

    ``rows`` / ``features`` of None leave the shape gates open (rows above
    the perm layouts' floor, a width the fused kernel admits): the static
    half ``make_grower`` starts from; ``_grow_impl`` completes it with the
    shapes it is traced at.  ``platform`` defaults to the live backend and
    exists so a CPU test can ask what a TPU would run."""
    import jax

    from ..ops.histogram import resolve_impl
    from ..ops.pallas_wave import wave_dtype_for, wave_layout

    for name, value, valid in (
            ("tpu_histogram_impl", cfg.histogram_impl, HIST_IMPLS),
            ("tpu_wave_kernel", cfg.wave_kernel, WAVE_KERNELS),
            ("tpu_hist_comm", cfg.hist_comm, HIST_COMMS)):
        if value not in valid:
            raise ValueError(
                f"{name}={value!r}: expected one of {', '.join(valid)}")
    platform = jax.default_backend() if platform is None else platform

    # ---- the shared facts, derived once
    sp = cfg.split
    dshards = 1 if mesh is None else int(mesh.shape[data_axis])
    others = [] if mesh is None else [a for a in mesh.axis_names
                                      if a != data_axis]
    fshards = int(mesh.shape[others[0]]) if len(others) == 1 else 1
    forced = bool(cfg.forced_splits)
    mono_refresh = ((cfg.mono_intermediate or cfg.mono_advanced)
                    and sp.has_monotone)
    per_node = (sp.extra_trees or cfg.feature_fraction_bynode < 1.0
                or bool(cfg.interaction_groups))
    shard_rows = None if rows is None else -(-rows // dshards)
    above_floor = shard_rows is None or shard_rows > PERM_MIN_ROWS
    floor_msg = (f"{shard_rows} rows a shard: the permutation layout "
                 f"starts above {PERM_MIN_ROWS}")
    why = {}

    # ---- layout and body.  A feature-only mesh runs the feature-sharded
    # wave where every enabled option scans per shard: per-node masks,
    # CEGB and feature_contri live in full feature space.
    fp_refused = _first(
        (fshards <= 1, "no feature mesh"),
        (dshards > 1, "the mesh shards rows as well"),
        (cfg.leaf_batch != 1, f"leaf_batch={cfg.leaf_batch}: the "
         "feature-sharded wave is a wave of one"),
        (cfg.voting, "voting-parallel"),
        (per_node, "extra_trees / feature_fraction_bynode / interaction "
         "constraints draw per-node masks in full feature space"),
        (sp.use_cegb, "CEGB penalties live in full feature space"),
        (forced, "forced splits read full-width histograms"),
        (cfg.bundled, "EFB bundling"),
        (bool(sp.feature_contri), "feature_contri is a full-width tuple"),
        (mono_refresh, "the intermediate/advanced monotone refresh"))
    if mesh is None:
        layout = "single"
        body = "wave" if cfg.gather_rows and above_floor else "mask"
    elif not fp_refused and above_floor:
        layout, body = "feature", "wave"
    elif cfg.gather_rows and above_floor:
        layout, body = "data", "wave"
    else:
        layout, body = "gspmd", "mask"
    mask_msg = (floor_msg if not above_floor
                else "gather_rows is off: the mask layout")
    if fshards > 1 and layout != "feature":
        why["feature"] = fp_refused or floor_msg

    # ---- histogram implementation: the mask body under a mesh runs on
    # GSPMD-sharded operands outside shard_map, where the per-device
    # Pallas kernel cannot go
    hist_impl = resolve_impl(cfg.histogram_impl, platform)
    if layout == "gspmd" and hist_impl == "pallas":
        hist_impl = "onehot" if platform == "tpu" else "segment"

    # ---- 4-bit bins: nibble pairs hold no bundle bin and must not
    # straddle feature shards (held to the composition, not the rows: the
    # caller packs once)
    if cfg.packed4:
        why["packed4"] = _first(
            (cfg.bundled, "EFB bundle bins exceed 4 bits"),
            (not fp_refused, "nibble pairs would straddle feature shards"))
    packed4 = cfg.packed4 and why["packed4"] is None

    # ---- cross-shard reduction of the data layout
    scatter_asked = dshards > 1 and cfg.hist_comm != "allreduce"
    if scatter_asked:
        why["scatter"] = _first(
            (layout != "data", mask_msg),
            (cfg.voting, "voting reduces only the vote winners' slices"),
            (forced, "forced splits read the full histogram row of an "
             "arbitrary feature"),
            (bool(sp.feature_contri) and not cfg.bundled,
             "feature_contri without EFB is a full-width tuple baked "
             "into the scan"),
            (mono_refresh, "the intermediate/advanced monotone refresh "
             "rescans every leaf from replicated histograms"))
    scatter = scatter_asked and why["scatter"] is None
    reduce = ("none" if layout != "data" else "vote" if cfg.voting
              else "scatter" if scatter else "psum")

    # ---- bounded histogram pool
    if cfg.histogram_pool_size >= 0:
        why["pool"] = _first(
            (layout == "feature", "the feature-sharded wave keeps every "
             "leaf histogram resident"),
            (body != "wave", mask_msg),
            (cfg.voting, "voting reads resident LOCAL parent histograms"),
            (mono_refresh, "the intermediate/advanced monotone refresh "
             "rescans every leaf from its resident histogram"))
    pool = cfg.histogram_pool_size >= 0 and why["pool"] is None

    # ---- fused wave kernel
    if cfg.wave_kernel != "unfused":
        why["fused"] = _first(
            (mesh is not None, "device mesh: the cross-shard reduce lands "
             "between build and scan"),
            (body != "wave", mask_msg),
            (cfg.voting, "voting scans compact vote-winner slices"),
            (cfg.bundled, "EFB bundling: the scan runs in expanded "
             "original-feature space"),
            (forced, "forced splits overwrite stored splits mid-growth"),
            (sp.has_monotone, "monotone constraints: the scan needs "
             "per-child output bounds"),
            (per_node, "extra_trees / feature_fraction_bynode / "
             "interaction constraints: per-node masks and thresholds"),
            (sp.use_cegb, "CEGB: per-child gain-penalty columns"),
            (bool(sp.feature_contri), "feature_contri: full-width "
             "multipliers"),
            (sp.has_categorical and sp.use_sorted_categorical,
             "sorted categoricals: the many-vs-many scan argsorts"),
            (cfg.wave_kernel == "auto" and hist_impl != "pallas",
             f"histogram implementation {hist_impl} on platform "
             f"{platform}: tpu_wave_kernel=auto fuses only beside the "
             "pallas kernel"))
        if why["fused"] is None and features is not None:
            bins, dtype = cfg.hist_bins or cfg.num_bins, wave_dtype_for(cfg)

            def fits(width):
                return wave_layout(width, bins, dtype, cfg.rows_block,
                                   packed4)["fits"]

            if not fits(features):
                widest = max(filter(fits, range(1, features)), default=0)
                why["fused"] = (f"{features} features: wave_layout admits "
                                f"up to {widest} at {bins} bins {dtype}")
    fused = cfg.wave_kernel != "unfused" and why["fused"] is None

    # ---- row sampling: a sampled tree is grown over the in-bag rows alone
    # where the selection hands the grower row ids of static length (the
    # device GOSS selection) and the body is the single-device wave, whose
    # perm holds row ids; every other composition weights all rows
    sampler = cfg.sampling
    if sampler != "none":
        why["subset"] = _first(
            (sampler == "goss_host", "the host GOSS sampler "
             "(tpu_device_goss=off) hands over a row mask"),
            (sampler != "goss_device", f"{sampler} hands over a row mask"),
            (mesh is not None, "device mesh: each shard holds its own "
             "share of the sample, of no static length"),
            (body != "wave", mask_msg))
    sampling = ("none" if sampler == "none"
                else "mask" if why["subset"] else "subset")

    # ---- the streamed trainer (lightgbm_tpu/stream/): a host-driven twin
    # of the mask body; every per-split pass must be row-separable
    why["stream"] = _first(
        (mesh is not None, "device mesh (stream residency is "
         "single-device)"),
        (cfg.voting, "voting-parallel keeps local histograms"),
        (cfg.bundled, "EFB bundling"),
        (forced, "forced splits"),
        (mono_refresh, "intermediate/advanced monotone refresh"),
        (sp.use_cegb, "CEGB penalties"),
        (bool(cfg.interaction_groups), "interaction constraints"))

    why = {k: v for k, v in why.items() if v is not None}
    return GrowthPlan(
        body=body, layout=layout, fused=fused, hist_impl=hist_impl,
        packed4=packed4, reduce=reduce, pool=pool,
        stream_reason=why.get("stream"), why=MappingProxyType(why),
        sampling=sampling)
