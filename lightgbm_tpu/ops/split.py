"""Best-split search over histograms.

Reference counterpart: ``FeatureHistogram::FindBestThreshold`` /
``FindBestThresholdSequentially`` (``src/treelearner/feature_histogram.hpp:165,832``)
— per-feature forward/backward scans with L1/L2 regularization, ``min_data_in_leaf``,
``min_sum_hessian_in_leaf``, ``min_gain_to_split`` and missing-value
default-direction handling; categorical one-hot splits; CUDA analog
``cuda_best_split_finder.cu``.

TPU re-design: instead of sequential per-feature scans, ALL features and ALL
thresholds are evaluated at once as cumulative sums over the padded (F, B)
histogram, with the two missing directions evaluated as two vectorized variants
(the reference's forward + backward scans).  Invalid candidates are masked to
``-inf`` and a single argmax picks the winner — this is the shape XLA/TPU wants:
no data-dependent control flow, one reduction.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_EPS = 1e-15


@dataclasses.dataclass(frozen=True)
class SplitConfig:
    """Static (compile-time) split hyper-parameters."""

    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    path_smooth: float = 0.0
    # Monotone split-gain penalty near the root (reference
    # ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:357).
    monotone_penalty: float = 0.0
    # Per-feature split-gain multipliers (reference feature_contri /
    # config->feature_contri applied in FindBestThreshold* gain).
    feature_contri: 'Optional[Tuple[float, ...]]' = None
    # Extremely-randomized trees (reference col_sampler + USE_RAND scans):
    # when set, each (node, feature) evaluates ONE random threshold.
    extra_trees: bool = False
    # Static dataset facts (set from the bin mappers) that let the compiled
    # scan skip whole candidate families.  True = "may be present" (safe).
    has_nan: bool = True
    has_categorical: bool = True
    # Any categorical feature with num_bins > max_cat_to_onehot (enables the
    # sorted many-vs-many scan; one-hot-only datasets skip it entirely).
    use_sorted_categorical: bool = True
    has_monotone: bool = True
    # Cost-effective gradient boosting (reference
    # ``cost_effective_gradient_boosting.hpp:79`` DeltaGain).
    use_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # Feature-block width for the scan's (F, B) cumsum/gain buffers: the
    # candidate evaluation runs per G-block through a sequential lax.map so
    # peak scan scratch stops scaling with full F (wide-feature shapes,
    # F=700/F=2000).  0 = auto (128-wide blocks once the scan width exceeds
    # 256 columns), 1 = untiled, >= 2 = explicit block width.  The winner is
    # selected with the exact tie-break order of the untiled argmax (lowest
    # flat index; sorted-categorical wins only strictly), so tiling never
    # changes the chosen split.
    scan_tile: int = 0


class BestSplit(NamedTuple):
    """Scalar split decision (reference ``SplitInfo``, ``split_info.hpp``)."""

    gain: jnp.ndarray          # f32; -inf when no valid split
    feature: jnp.ndarray       # i32
    bin: jnp.ndarray           # i32 threshold bin (numerical: go left if bin<=t)
    default_left: jnp.ndarray  # bool: NaN direction
    is_cat: jnp.ndarray        # bool
    cat_mask: jnp.ndarray      # (B,) bool: bins going LEFT (categorical only)
    sum_grad_left: jnp.ndarray
    sum_hess_left: jnp.ndarray
    count_left: jnp.ndarray
    sum_grad_right: jnp.ndarray
    sum_hess_right: jnp.ndarray
    count_right: jnp.ndarray


def sync_best_split(bs: "BestSplit", feature_offset, axis: str,
                    n_shards: int) -> "BestSplit":
    """Globalize per-shard slice-local winners (reference
    ``SyncUpGlobalBestSplit``, ``parallel_tree_learner.h`` /
    ``feature_parallel_tree_learner.cpp:59-77``).

    Each shard ran :func:`best_split` over only the feature slice it owns —
    the feature-parallel layout's sharded columns, or the data-parallel
    reduce-scatter path's owned block of the reduced histograms
    (``data_parallel_tree_learner.cpp:284``).  The winner's SplitInfo
    (scalars + categorical mask) is broadcast by a one-hot psum; LOCAL
    feature indices become GLOBAL by adding this shard's
    ``feature_offset``.  Ties break to the lowest shard, like the
    reference's rank order — for contiguous ascending feature slices that
    is exactly the replicated scan's lowest-flat-index argmax.

    Precision note: the f32 payload transports counts/sums losslessly —
    the psum has exactly one non-zero contributor per element, so the
    received value bit-equals the sender's.  Counts are f32 BEFORE the
    payload in every path (f32 histogram count channel, f32 cumsum in
    the split scan, f32 GrowthState.leaf_count; the quantized path
    converts int32→f32 before scanning), so serial and sharded share the
    same >2^24 representation limit and cannot drift apart at this sync.
    The feature index rides exactly up to 2^24 features.  Works on scalar
    or batched (vmapped) BestSplits."""
    neg_inf = -jnp.inf

    def one(gain, feature, sbin, dl, ic, cmask, gl, hl, cl, gr, hr, cr):
        win = jax.lax.pmax(gain, axis)
        sidx = jax.lax.axis_index(axis)
        is_w = (gain >= win) & (win > neg_inf)
        first = jax.lax.pmin(jnp.where(is_w, sidx, n_shards), axis)
        mine = sidx == first
        scal = jnp.stack([
            (feature + feature_offset).astype(jnp.float32),
            sbin.astype(jnp.float32), dl.astype(jnp.float32),
            ic.astype(jnp.float32), gl, hl, cl, gr, hr, cr])
        payload = jnp.concatenate([scal, cmask.astype(jnp.float32)])
        payload = jax.lax.psum(
            jnp.where(mine, payload, jnp.zeros_like(payload)), axis)
        return BestSplit(
            gain=win,
            feature=jnp.round(payload[0]).astype(jnp.int32),
            bin=jnp.round(payload[1]).astype(jnp.int32),
            default_left=payload[2] > 0.5,
            is_cat=payload[3] > 0.5,
            cat_mask=payload[10:] > 0.5,
            sum_grad_left=payload[4], sum_hess_left=payload[5],
            count_left=payload[6],
            sum_grad_right=payload[7], sum_hess_right=payload[8],
            count_right=payload[9])

    args = (bs.gain, bs.feature, bs.bin, bs.default_left, bs.is_cat,
            bs.cat_mask, bs.sum_grad_left, bs.sum_hess_left,
            bs.count_left, bs.sum_grad_right, bs.sum_hess_right,
            bs.count_right)
    if bs.gain.ndim == 0:
        return one(*args)
    return jax.vmap(one)(*args)


def threshold_l1(s: jnp.ndarray, l1: float) -> jnp.ndarray:
    """ThresholdL1 (reference ``feature_histogram.hpp`` GetLeafGain helpers)."""
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(g, h, cfg: SplitConfig, l2_extra: float = 0.0):
    """Optimal leaf value −ThresholdL1(G, l1)/(H + l2), with ``max_delta_step``
    clamping (reference ``CalculateSplittedLeafOutput``)."""
    out = -threshold_l1(g, cfg.lambda_l1) / (h + cfg.lambda_l2 + l2_extra + _EPS)
    if cfg.max_delta_step > 0.0:
        out = jnp.clip(out, -cfg.max_delta_step, cfg.max_delta_step)
    return out


def leaf_gain(g, h, cfg: SplitConfig, l2_extra: float = 0.0):
    t = threshold_l1(g, cfg.lambda_l1)
    return (t * t) / (h + cfg.lambda_l2 + l2_extra + _EPS)


def smoothed_output(g, h, count, parent_output, cfg: SplitConfig,
                    l2_extra: float = 0.0):
    """``CalculateSplittedLeafOutput`` with path smoothing (reference
    ``feature_histogram.hpp``): ``w*(n/s)/(n/s+1) + parent/(n/s+1)``."""
    w = leaf_output(g, h, cfg, l2_extra)
    if cfg.path_smooth <= 0.0:
        return w
    ratio = count / cfg.path_smooth
    return w * ratio / (ratio + 1.0) + parent_output / (ratio + 1.0)


def gain_given_output(g, h, out, cfg: SplitConfig, l2_extra: float = 0.0):
    """``GetLeafGainGivenOutput``: ``-(2*TL1(g)*w + (h+l2)*w^2)``."""
    t = threshold_l1(g, cfg.lambda_l1)
    return -(2.0 * t * out + (h + cfg.lambda_l2 + l2_extra) * out * out)


def child_gain(g, h, count, parent_output, cfg: SplitConfig,
               l2_extra: float = 0.0, out_lo=None, out_hi=None):
    """Per-child gain; closed form without smoothing/constraints,
    output-based otherwise (reference GetSplitGains USE_SMOOTHING/USE_MC
    dispatch: outputs clipped to the leaf's monotone bounds)."""
    if cfg.path_smooth <= 0.0 and out_lo is None:
        return leaf_gain(g, h, cfg, l2_extra)
    w = smoothed_output(g, h, count, parent_output, cfg, l2_extra)
    if out_lo is not None:
        w = jnp.clip(w, out_lo, out_hi)
    return gain_given_output(g, h, w, cfg, l2_extra)


def _sorted_categorical(G, H, C, parent_grad, parent_hess, parent_count,
                        parent_output, in_feature, cfg: SplitConfig,
                        min_count: float, rand_bins=None):
    """Sorted many-vs-many categorical scan (reference
    ``FindBestThresholdCategoricalInner`` sorted branch,
    ``feature_histogram.cpp:241-340``): bins with enough data are sorted by
    ``grad/(hess+cat_smooth)``; prefixes of length <= ``max_cat_threshold``
    are scanned from both ends with ``min_data_per_group`` grouping; child
    gains use ``l2 + cat_l2``.

    Returns per-feature ``(gain, cat_mask, gl, hl, cl)``; gain is the child
    sum (the caller subtracts the parent gain shift).
    """
    f, b = G.shape
    K = min(b, max(int(cfg.max_cat_threshold), 1))
    mdpg = float(cfg.min_data_per_group)
    valid = in_feature & (C >= cfg.cat_smooth)
    ctr = G / (H + cfg.cat_smooth)
    key = jnp.where(valid, ctr, jnp.inf)
    order = jnp.argsort(key, axis=1, stable=True)              # (F, B)
    rank = jnp.argsort(order, axis=1)                          # inverse perm
    used = jnp.sum(valid, axis=1).astype(jnp.int32)            # (F,)
    vs = jnp.take_along_axis(valid, order, axis=1)
    Gs = jnp.where(vs, jnp.take_along_axis(G, order, axis=1), 0.0)
    Hs = jnp.where(vs, jnp.take_along_axis(H, order, axis=1), 0.0)
    Cs = jnp.where(vs, jnp.take_along_axis(C, order, axis=1), 0.0)
    max_num_cat = jnp.minimum(cfg.max_cat_threshold, (used + 1) // 2)
    iidx = jnp.arange(K, dtype=jnp.int32)[None, :]             # (1, K)
    rand_pos = None
    if rand_bins is not None:
        max_thr = jnp.maximum(jnp.minimum(max_num_cat, used) - 1, 0) + 1
        rand_pos = (rand_bins % max_thr)[:, None]

    def direction(Gd, Hd, Cd):
        cg = jnp.cumsum(Gd, axis=1)
        ch = jnp.cumsum(Hd, axis=1) + _EPS
        cc = jnp.cumsum(Cd, axis=1)
        pos_ok = (iidx < used[:, None]) & (iidx < max_num_cat[:, None])
        left_ok = (cc >= min_count) & (ch >= cfg.min_sum_hessian_in_leaf)
        rc = parent_count - cc
        rh = parent_hess - ch
        right_ok = ((rc >= min_count) & (rc >= mdpg)
                    & (rh >= cfg.min_sum_hessian_in_leaf))
        ok = pos_ok & left_ok & right_ok

        def step(carry, x):
            cnt_i, ok_i = x
            acc = carry + cnt_i
            cand = ok_i & (acc >= mdpg)
            return jnp.where(cand, 0.0, acc), cand

        _, emit = jax.lax.scan(step, jnp.zeros(f, cg.dtype),
                               (Cd.T, ok.T))
        emit = emit.T                                          # (F, K)
        if rand_pos is not None:
            emit = emit & (iidx == rand_pos)
        gl, hl, cl = cg, ch, cc
        gr, hr, cr = (parent_grad - gl, parent_hess - hl, parent_count - cl)
        gain = (child_gain(gl, hl, cl, parent_output, cfg, cfg.cat_l2)
                + child_gain(gr, hr, cr, parent_output, cfg, cfg.cat_l2))
        return jnp.where(emit, gain, -jnp.inf), gl, hl, cl

    gain_f, glf, hlf, clf = direction(Gs[:, :K], Hs[:, :K], Cs[:, :K])
    # Backward direction starts at the last USED position per feature.
    bidx = jnp.clip(used[:, None] - 1 - iidx, 0, b - 1)        # (F, K)
    in_back = iidx < used[:, None]
    Gb = jnp.where(in_back, jnp.take_along_axis(Gs, bidx, axis=1), 0.0)
    Hb = jnp.where(in_back, jnp.take_along_axis(Hs, bidx, axis=1), 0.0)
    Cb = jnp.where(in_back, jnp.take_along_axis(Cs, bidx, axis=1), 0.0)
    gain_b, glb, hlb, clb = direction(Gb, Hb, Cb)

    gain2 = jnp.stack([gain_f, gain_b], axis=1)                # (F, 2, K)
    flat = jnp.argmax(gain2.reshape(f, 2 * K), axis=1)
    best_dir = (flat // K).astype(jnp.int32)                   # 0 fwd, 1 bwd
    best_i = (flat % K).astype(jnp.int32)
    take = lambda a2: jnp.take_along_axis(
        a2.reshape(f, 2 * K), flat[:, None], axis=1)[:, 0]
    gain = take(gain2)
    gl = take(jnp.stack([glf, glb], axis=1))
    hl = take(jnp.stack([hlf, hlb], axis=1))
    cl = take(jnp.stack([clf, clb], axis=1))
    # cat_mask: the chosen prefix of the sorted order routes LEFT.
    fwd_mask = rank <= best_i[:, None]
    bwd_mask = rank >= (used - 1 - best_i)[:, None]
    cat_mask = valid & jnp.where((best_dir == 0)[:, None], fwd_mask, bwd_mask)
    return gain, cat_mask, gl, hl, cl


def _resolve_tile(scan_tile: int, f: int) -> int:
    """Effective G-block width for a scan over ``f`` columns (0 = untiled).
    Auto (0) engages 128-wide blocks only once the width exceeds 256 —
    narrow shapes keep the single fused scan they always had."""
    if scan_tile >= 2:
        return 0 if scan_tile >= f else scan_tile
    if scan_tile == 1:
        return 0
    return 128 if f > 256 else 0


class _ScanTables(NamedTuple):
    """Candidate tables of one (F, B) scan block — everything the argmax
    selection and the sorted-categorical merge consume.  Produced by
    :func:`scan_tables`, the half of the split scan that is callable from
    INSIDE a Pallas kernel (ops/pallas_wave.py): pure elementwise/cumsum
    arithmetic over (F, B) blocks — no argsort, no dynamic indexing."""

    gain_fb: jnp.ndarray           # (F, B) masked candidate gains
    num_default_left: jnp.ndarray  # (F, B) bool NaN direction of num. wins
    stats_mr: tuple                # 6x (F, B) child stats, NaN -> right
    stats_ml: tuple                # 6x (F, B) child stats, NaN -> left
    cat_stats: tuple               # 6x (F, B) child stats, one-hot cat.
    parent_gain: jnp.ndarray       # scalar parent gain shift
    parent_output: jnp.ndarray     # scalar resolved parent output
    in_feature: jnp.ndarray        # (F, B) bool valid-bin mask
    sorted_eligible: Optional[jnp.ndarray]  # (F, 1) sorted-cat eligibility
    penalty_col: Optional[jnp.ndarray]      # (F, 1) CEGB penalty column
    min_count: float


def _col(a):
    """Per-feature vector as an (F, 1) column.  The host paths pass (F,)
    vectors; the Pallas kernel passes (F, 1) columns (Mosaic dislikes 1D
    operands and lane-dim transposes), and broadcasting against (F, B)
    blocks is identical either way."""
    return a if a.ndim == 2 else a[:, None]


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the bin axis in log2(B) shift-and-add
    steps (Hillis-Steele).  Pallas TPU has no lowering for ``cumsum``;
    zero-filled shifts and elementwise adds lower everywhere, and they fix
    the ORDER of the f32 additions: the fused kernel (Mosaic or
    interpreted) and the XLA scan produce the same bits from the same
    histogram on any backend, which no matmul or backend-chosen scan
    promises."""
    b = x.shape[-1]
    k = 1
    while k < b:
        shifted = jnp.concatenate(
            [jnp.zeros(x.shape[:-1] + (k,), x.dtype), x[..., :-k]], axis=-1)
        x = x + shifted
        k *= 2
    return x


def scan_tables(
    G: jnp.ndarray,               # (F, B) grad sums (f32, scaled)
    H: jnp.ndarray,               # (F, B) hess sums
    C: jnp.ndarray,               # (F, B) counts
    parent_grad: jnp.ndarray,     # scalar ΣG over the leaf (incl. NaN bin)
    parent_hess: jnp.ndarray,     # scalar ΣH
    parent_count: jnp.ndarray,    # scalar rows
    *,
    num_bins_per_feature: jnp.ndarray,  # (F,)/(F,1) i32 (incl. NaN bin)
    nan_bins: jnp.ndarray,              # (F,)/(F,1) i32; == B when no NaN bin
    is_categorical: jnp.ndarray,        # (F,)/(F,1) bool
    feature_mask: jnp.ndarray,          # (F,)/(F,1) bool
    cfg: SplitConfig,
    monotone: jnp.ndarray | None = None,       # (F,) i32 in {-1,0,1}
    gain_penalty: jnp.ndarray | None = None,   # (F,) CEGB DeltaGain
    parent_output: jnp.ndarray | None = None,  # scalar (path_smooth anchor)
    rand_bins: jnp.ndarray | None = None,      # (F,) i32 (extra_trees)
    out_lo: jnp.ndarray | None = None,         # scalar monotone lower bound
    out_hi: jnp.ndarray | None = None,         # scalar monotone upper bound
    adv_bounds: tuple | None = None,           # advanced monotone (F, B) x4
    leaf_depth: jnp.ndarray | None = None,     # scalar (monotone_penalty)
    feature_contri: jnp.ndarray | None = None,  # (F,) f32 gain multipliers
) -> _ScanTables:
    """Evaluate every (feature, threshold, missing-direction) candidate of
    one (F, B) histogram block into masked gain/stat tables.  Phantom bins
    (``bin >= num_bins_per_feature[f]``, e.g. the fused kernel's
    lane-padded columns) are masked to ``-inf`` so a wider B never changes
    the candidate set."""
    f, b = G.shape
    nbpf_c = _col(num_bins_per_feature)
    nanb_c = _col(nan_bins)
    fmask_c = _col(feature_mask)
    biota = jax.lax.broadcasted_iota(jnp.int32, (f, b), 1)
    in_feature = biota < nbpf_c
    nan_pos = biota == nanb_c
    value_mask = in_feature & ~nan_pos
    if parent_output is None:
        parent_output = leaf_output(parent_grad, parent_hess, cfg)

    Gv = jnp.where(value_mask, G, 0.0)
    Hv = jnp.where(value_mask, H, 0.0)
    Cv = jnp.where(value_mask, C, 0.0)
    Gn = jnp.sum(jnp.where(nan_pos, G, 0.0), axis=1, keepdims=True)  # (F,1)
    Hn = jnp.sum(jnp.where(nan_pos, H, 0.0), axis=1, keepdims=True)
    Cn = jnp.sum(jnp.where(nan_pos, C, 0.0), axis=1, keepdims=True)

    cumG = _prefix_sum(Gv)
    cumH = _prefix_sum(Hv)
    cumC = _prefix_sum(Cv)

    # Parent gain shift: closed form without smoothing, output-based with
    # (reference BeforeNumerical / FindBestThresholdCategoricalInner).
    if cfg.path_smooth > 0.0:
        parent_gain = gain_given_output(parent_grad, parent_hess,
                                        parent_output, cfg)
    else:
        parent_gain = leaf_gain(parent_grad, parent_hess, cfg)
    min_count = float(max(cfg.min_data_in_leaf, 1))

    mono_bounds = (out_lo is not None and out_hi is not None
                   and cfg.has_monotone)
    blo = out_lo if mono_bounds else None
    bhi = out_hi if mono_bounds else None
    # Advanced monotone mode (reference AdvancedLeafConstraints,
    # monotone_constraints.hpp:583): numerical candidates clip each child to
    # its PER-THRESHOLD bound slice instead of the whole-leaf scalar;
    # categorical columns (not covered by the reference's slice machinery
    # either) fall back to the scalar leaf bounds.
    use_adv = adv_bounds is not None and cfg.has_monotone
    if use_adv:
        icc0 = _col(is_categorical)
        s_lo = blo if mono_bounds else -jnp.inf
        s_hi = bhi if mono_bounds else jnp.inf
        a_llo = jnp.where(icc0, s_lo, adv_bounds[0])
        a_lhi = jnp.where(icc0, s_hi, adv_bounds[1])
        a_rlo = jnp.where(icc0, s_lo, adv_bounds[2])
        a_rhi = jnp.where(icc0, s_hi, adv_bounds[3])
        num_lb, num_rb = (a_llo, a_lhi), (a_rlo, a_rhi)
    else:
        num_lb = num_rb = None

    def eval_dir(GL, HL, CL, l2_extra=0.0, lb=None, rb=None):
        GR = parent_grad - GL
        HR = parent_hess - HL
        CR = parent_count - CL
        valid = (
            (CL >= min_count)
            & (CR >= min_count)
            & (HL >= cfg.min_sum_hessian_in_leaf)
            & (HR >= cfg.min_sum_hessian_in_leaf)
        )
        llo, lhi = lb if lb is not None else (blo, bhi)
        rlo, rhi = rb if rb is not None else (blo, bhi)
        gain = (child_gain(GL, HL, CL, parent_output, cfg, l2_extra, llo, lhi)
                + child_gain(GR, HR, CR, parent_output, cfg, l2_extra,
                             rlo, rhi)
                - parent_gain)
        gain = jnp.where(valid & (gain > cfg.min_gain_to_split + _EPS), gain, -jnp.inf)
        return gain, (GL, HL, CL, GR, HR, CR)

    # Numerical: threshold t means "value-bin <= t goes left".
    gain_mr, stats_mr = eval_dir(cumG, cumH, cumC,
                                 lb=num_lb, rb=num_rb)                # NaN -> right
    if cfg.has_nan:
        gain_ml, stats_ml = eval_dir(cumG + Gn, cumH + Hn, cumC + Cn,
                                     lb=num_lb, rb=num_rb)            # NaN -> left
        # Without a NaN bin both directions coincide; keep missing-right.
        has_nan = nanb_c < b
        gain_ml = jnp.where(has_nan, gain_ml, -jnp.inf)
        num_gain = jnp.maximum(gain_mr, gain_ml)
        num_default_left = gain_ml > gain_mr
    else:
        stats_ml = stats_mr
        num_gain = gain_mr
        num_default_left = jnp.zeros_like(gain_mr, bool)
    num_gain = jnp.where(value_mask, num_gain, -jnp.inf)

    if cfg.has_categorical:
        # One-hot categorical: "bin == k goes left" (reference one-hot branch
        # of FindBestThresholdCategoricalInner — plain lambda_l2, not cat_l2,
        # which only applies in the sorted branch).
        cat_gain, cat_stats = eval_dir(G, H, C)
        cat_gain = jnp.where(in_feature, cat_gain, -jnp.inf)
        # Sorted features are excluded from the one-hot table; they compete
        # through the per-feature sorted scan merged by the caller.
        sorted_eligible = (_col(is_categorical)
                           & (nbpf_c > cfg.max_cat_to_onehot))
        is_cat_col = _col(is_categorical)
        gain_fb = jnp.where(is_cat_col, cat_gain, num_gain)
        gain_fb = jnp.where(sorted_eligible, -jnp.inf, gain_fb)
    else:
        cat_stats = stats_mr
        sorted_eligible = None
        is_cat_col = jnp.zeros((f, 1), bool)
        gain_fb = num_gain

    if rand_bins is not None and cfg.extra_trees:
        # extra_trees (reference USE_RAND scans): one random threshold per
        # (node, feature); all other candidates are masked out.
        gain_fb = jnp.where(biota == _col(rand_bins), gain_fb, -jnp.inf)

    if monotone is not None and cfg.has_monotone:
        # Basic monotone mode: reject splits whose child outputs violate the
        # direction (reference monotone_constraints.hpp BasicLeafConstraints).
        GLm = jnp.where(is_cat_col, cat_stats[0], jnp.where(num_default_left,
                        stats_ml[0], stats_mr[0]))
        HLm = jnp.where(is_cat_col, cat_stats[1], jnp.where(num_default_left,
                        stats_ml[1], stats_mr[1]))
        GRm = parent_grad - GLm
        HRm = parent_hess - HLm
        out_l = leaf_output(GLm, HLm, cfg)
        out_r = leaf_output(GRm, HRm, cfg)
        if use_adv:
            out_l = jnp.clip(out_l, a_llo, a_lhi)
            out_r = jnp.clip(out_r, a_rlo, a_rhi)
        elif mono_bounds:
            out_l = jnp.clip(out_l, blo, bhi)
            out_r = jnp.clip(out_r, blo, bhi)
        mono = _col(monotone)
        viol = ((mono > 0) & (out_l > out_r)) | ((mono < 0) & (out_l < out_r))
        gain_fb = jnp.where(viol, -jnp.inf, gain_fb)
        if cfg.monotone_penalty > 0.0 and leaf_depth is not None:
            # reference ComputeMonotoneSplitGainPenalty
            # (monotone_constraints.hpp:357): multiplies the gain of splits
            # on monotone features, fading with depth.
            p = cfg.monotone_penalty
            d = leaf_depth.astype(jnp.float32)
            pen = jnp.where(
                p >= d + 1.0, _EPS,
                jnp.where(p <= 1.0, 1.0 - p / (2.0 ** d) + _EPS,
                          1.0 - 2.0 ** (p - 1.0 - d) + _EPS))
            gain_fb = jnp.where(mono != 0, gain_fb * pen, gain_fb)

    penalty_col = None
    if gain_penalty is not None and cfg.use_cegb:
        penalty_col = _col(gain_penalty)
        gain_fb = gain_fb - penalty_col
        # Penalized gains that drop to <= 0 are no longer worth splitting
        # (reference stops on "gain <= 0").
        gain_fb = jnp.where(gain_fb > _EPS, gain_fb, -jnp.inf)

    if feature_contri is not None:
        scaled = gain_fb * _col(feature_contri)
        # reference stops on best gain <= 0: a zeroed-out feature must not
        # win over "no split"
        gain_fb = jnp.where(jnp.isfinite(gain_fb) & (scaled > _EPS),
                            scaled, -jnp.inf)
    gain_fb = jnp.where(fmask_c, gain_fb, -jnp.inf)

    return _ScanTables(
        gain_fb=gain_fb, num_default_left=num_default_left,
        stats_mr=stats_mr, stats_ml=stats_ml, cat_stats=cat_stats,
        parent_gain=parent_gain, parent_output=parent_output,
        in_feature=in_feature, sorted_eligible=sorted_eligible,
        penalty_col=penalty_col, min_count=min_count)


def _select_from_tables(t: _ScanTables, is_categorical, cfg: SplitConfig
                        ) -> BestSplit:
    """Argmax + winner-stat gather over the scan tables (the host half):
    lowest flat (feature, bin) index wins ties — the tie-break every other
    reducer in the framework replays.  Must stay selection-identical to
    :func:`select_payload` (the Pallas-safe one-hot variant; pinned in
    tests/test_wave_fused.py)."""
    gain_fb = t.gain_fb
    f, b = gain_fb.shape
    flat = jnp.argmax(gain_fb)
    bf = (flat // b).astype(jnp.int32)
    bb = (flat % b).astype(jnp.int32)
    bgain = gain_fb[bf, bb]
    bis_cat = (is_categorical[bf] if cfg.has_categorical
               else jnp.asarray(False))
    bdefault_left = jnp.where(bis_cat, False, t.num_default_left[bf, bb])

    def pick(stats_cat, stats_numl, stats_numr, i):
        return jnp.where(
            bis_cat, stats_cat[i][bf, bb],
            jnp.where(bdefault_left, stats_numl[i][bf, bb], stats_numr[i][bf, bb]),
        )

    GL, HL, CL, GR, HR, CR = (pick(t.cat_stats, t.stats_ml, t.stats_mr, i)
                              for i in range(6))
    cat_mask = (jnp.arange(b, dtype=jnp.int32) == bb) & bis_cat

    return BestSplit(
        gain=bgain, feature=bf, bin=bb,
        default_left=bdefault_left, is_cat=bis_cat, cat_mask=cat_mask,
        sum_grad_left=GL, sum_hess_left=HL, count_left=CL,
        sum_grad_right=GR, sum_hess_right=HR, count_right=CR,
    )


def select_payload(t: _ScanTables, is_categorical, cfg: SplitConfig, *,
                   flat_keys=None, key_bins: int = 0):
    """Mosaic-safe winner selection: the same max-gain / lowest-flat-key
    tie-break as :func:`_select_from_tables`'s ``argmax``, expressed as a
    full-block max + one-hot masked gathers (no dynamic indexing, which
    Pallas TPU kernels cannot lower).  The extracted values are exact —
    each gather sums exactly one selected element.

    ``flat_keys`` (int32, same shape as the gain table) assigns every
    candidate its tie-break priority; lower wins.  The default row-major
    ``feat * B + bin`` reproduces ``argmax`` exactly; the fused kernel's
    packed4 path passes ORIGINAL-feature-order keys so the nibble-plane
    layout cannot perturb the tie-break.  Candidates keyed ``INT32_MAX``
    (phantom lane-padding) can win only if every real candidate is also
    ``-inf`` — and every real key < INT32_MAX, so they never do.

    Returns the scalar tuple ``(gain, feature, bin, default_left, is_cat,
    GL, HL, CL, GR, HR, CR)`` with feature/bin decoded through
    ``key_bins`` (defaults to the table width)."""
    gain_fb = t.gain_fb
    f, b = gain_fb.shape
    key_bins = key_bins or b
    if flat_keys is None:
        flat_keys = (jax.lax.broadcasted_iota(jnp.int32, (f, b), 0) * b
                     + jax.lax.broadcasted_iota(jnp.int32, (f, b), 1))
    imax = jnp.iinfo(jnp.int32).max
    mx = jnp.max(gain_fb)
    tie = gain_fb == mx
    kwin = jnp.min(jnp.where(tie, flat_keys, imax))
    sel = tie & (flat_keys == kwin)
    bf = (kwin // key_bins).astype(jnp.int32)
    bb = (kwin % key_bins).astype(jnp.int32)
    bgain = jnp.max(jnp.where(sel, gain_fb, -jnp.inf))
    if cfg.has_categorical:
        bis_cat = jnp.any(sel & _col(is_categorical))
    else:
        bis_cat = jnp.asarray(False)
    bdefault_left = jnp.where(bis_cat, False,
                              jnp.any(sel & t.num_default_left))

    def take(a):
        return jnp.sum(jnp.where(sel, a, 0.0))

    def pick(i):
        return jnp.where(
            bis_cat, take(t.cat_stats[i]),
            jnp.where(bdefault_left, take(t.stats_ml[i]),
                      take(t.stats_mr[i])))

    GL, HL, CL, GR, HR, CR = (pick(i) for i in range(6))
    return bgain, bf, bb, bdefault_left, bis_cat, GL, HL, CL, GR, HR, CR


@functools.partial(jax.jit, static_argnames=("cfg", "with_feature_gains"))
def _best_split_impl(
    hist: jnp.ndarray,            # (F, B, 3) leaf histogram
    parent_grad: jnp.ndarray,     # scalar ΣG over the leaf (includes NaN bin)
    parent_hess: jnp.ndarray,     # scalar ΣH
    parent_count: jnp.ndarray,    # scalar rows
    *,
    num_bins_per_feature: jnp.ndarray,  # (F,) i32 (includes NaN bin if present)
    nan_bins: jnp.ndarray,              # (F,) i32; == B when feature has no NaN bin
    is_categorical: jnp.ndarray,        # (F,) bool
    monotone: jnp.ndarray | None,       # (F,) i32 in {-1,0,1} or None
    feature_mask: jnp.ndarray,          # (F,) bool (feature_fraction / interaction)
    cfg: SplitConfig,
    gain_penalty: jnp.ndarray | None = None,  # (F,) subtracted from every gain
                                              # (CEGB DeltaGain)
    parent_output: jnp.ndarray | None = None,  # scalar leaf output
                                               # (path_smooth anchor)
    rand_bins: jnp.ndarray | None = None,      # (F,) i32 random threshold per
                                               # feature (extra_trees)
    out_lo: jnp.ndarray | None = None,         # scalar monotone lower bound
    out_hi: jnp.ndarray | None = None,         # scalar monotone upper bound
    adv_bounds: tuple | None = None,           # advanced monotone mode:
                                               # (LLO, LHI, RLO, RHI) each
                                               # (F, B) — per-threshold child
                                               # output bounds (reference
                                               # AdvancedLeafConstraints
                                               # cumulative slices)
    leaf_depth: jnp.ndarray | None = None,     # scalar (monotone_penalty)
    feature_contri: jnp.ndarray | None = None,  # (F,) f32 gain multipliers,
                                                # pre-resolved by best_split
    with_feature_gains: bool = False,          # also return (F,) best gain per
                                               # feature (voting-parallel)
):
    """One scan over an (F, B, 3) histogram block (the whole feature space
    untiled, or one G-block of it).  Returns ``(best, from_sorted, fg)``
    where ``from_sorted`` flags a sorted-categorical winner — the cross-tile
    reducer needs it to reproduce the untiled "sorted wins only strictly"
    rule — and ``fg`` is the per-feature gain vector (None unless
    ``with_feature_gains``).

    Jitted, so that the untiled scan is ONE compiled expression wherever it
    is called from, as a G-block under ``lax.map`` always is.  Op by op
    (an eager call) every ``a * b + c`` rounds twice; inside a compiled
    fusion the CPU backend's LLVM contracts it into one fused multiply-add
    (``gain_given_output`` under ``path_smooth`` is such a sum), and the
    per-feature gains of the two forms then sit 1 ulp apart.  Compiled
    against compiled they are the same bits (tests/test_split_tile.py);
    inside an enclosing jit this wrapper is inlined and changes nothing."""
    G, H, C = hist[..., 0], hist[..., 1], hist[..., 2]
    t = scan_tables(
        G, H, C, parent_grad, parent_hess, parent_count,
        num_bins_per_feature=num_bins_per_feature, nan_bins=nan_bins,
        is_categorical=is_categorical, feature_mask=feature_mask, cfg=cfg,
        monotone=monotone, gain_penalty=gain_penalty,
        parent_output=parent_output, rand_bins=rand_bins,
        out_lo=out_lo, out_hi=out_hi, adv_bounds=adv_bounds,
        leaf_depth=leaf_depth, feature_contri=feature_contri)
    best = _select_from_tables(t, is_categorical, cfg)

    from_sorted = jnp.asarray(False)
    if cfg.has_categorical and cfg.use_sorted_categorical:
        best, from_sorted = _merge_sorted_categorical(
            best, G, H, C, parent_grad, parent_hess, parent_count,
            t.parent_output, t.parent_gain, t.in_feature,
            t.sorted_eligible[:, 0], feature_mask, t.penalty_col, cfg,
            t.min_count, rand_bins if cfg.extra_trees else None,
            feature_contri)
    fg = None
    if with_feature_gains:
        fg = jnp.max(t.gain_fb, axis=1)
        # NOTE: sorted-categorical gains are not folded into the vote — the
        # vote only ranks features, and one-hot gains rank the same columns.
    return best, from_sorted, fg


def best_split(
    hist: jnp.ndarray,            # (F, B, 3) leaf histogram
    parent_grad: jnp.ndarray,
    parent_hess: jnp.ndarray,
    parent_count: jnp.ndarray,
    *,
    num_bins_per_feature: jnp.ndarray,
    nan_bins: jnp.ndarray,
    is_categorical: jnp.ndarray,
    monotone: jnp.ndarray | None,
    feature_mask: jnp.ndarray,
    cfg: SplitConfig,
    gain_penalty: jnp.ndarray | None = None,
    parent_output: jnp.ndarray | None = None,
    rand_bins: jnp.ndarray | None = None,
    out_lo: jnp.ndarray | None = None,
    out_hi: jnp.ndarray | None = None,
    adv_bounds: tuple | None = None,
    leaf_depth: jnp.ndarray | None = None,
    with_feature_gains: bool = False,
) -> BestSplit:
    """Evaluate every (feature, threshold, missing-direction) candidate and
    argmax (argument semantics documented on :func:`_best_split_impl`).

    With ``with_feature_gains`` returns ``(best, per_feature_gain)`` — the
    local vote input of the voting-parallel learner (reference
    ``VotingParallelTreeLearner``, ``voting_parallel_tree_learner.cpp``).

    Wide feature spaces (``cfg.scan_tile``) evaluate in G-blocks through a
    sequential ``lax.map`` so the (F, B) cumsum/gain scratch peaks at one
    block instead of full F; the cross-block reduction replays the untiled
    tie-break order exactly (lowest flat index within a block, lowest block
    across blocks, sorted-categorical winners only on strictly greater
    gain), so the chosen split is identical to the untiled scan."""
    f, b, _ = hist.shape
    fc = None
    if cfg.feature_contri is not None:
        fc = jnp.asarray(cfg.feature_contri, jnp.float32)[:f]
        if fc.shape[0] < f:
            fc = jnp.concatenate(
                [fc, jnp.ones(f - fc.shape[0], jnp.float32)])
    t = _resolve_tile(cfg.scan_tile, f)
    if t == 0:
        best, _src, fg = _best_split_impl(
            hist, parent_grad, parent_hess, parent_count,
            num_bins_per_feature=num_bins_per_feature, nan_bins=nan_bins,
            is_categorical=is_categorical, monotone=monotone,
            feature_mask=feature_mask, cfg=cfg, gain_penalty=gain_penalty,
            parent_output=parent_output, rand_bins=rand_bins,
            out_lo=out_lo, out_hi=out_hi, adv_bounds=adv_bounds,
            leaf_depth=leaf_depth, feature_contri=fc,
            with_feature_gains=with_feature_gains)
        return (best, fg) if with_feature_gains else best

    nt = -(-f // t)
    pad = nt * t - f

    def blk(a, fill):
        """(F, ...) per-feature array -> (nt, t, ...) padded G-blocks.
        Pad columns are inert: nbpf=0 masks them out of every candidate."""
        if a is None:
            return None
        if pad:
            a = jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])
        return a.reshape((nt, t) + a.shape[1:])

    ops = {"hist": blk(hist, 0), "nbpf": blk(num_bins_per_feature, 0),
           "nanb": blk(nan_bins, b), "iscat": blk(is_categorical, False),
           "fmask": blk(feature_mask, False)}
    if monotone is not None:
        ops["mono"] = blk(monotone, 0)
    if gain_penalty is not None:
        ops["pen"] = blk(gain_penalty, 0.0)
    if rand_bins is not None:
        ops["rand"] = blk(rand_bins, 0)
    if fc is not None:
        ops["fc"] = blk(fc, 1.0)
    if adv_bounds is not None:
        for i, a in enumerate(adv_bounds):
            ops[f"adv{i}"] = blk(a, 0.0)

    def tile_fn(x):
        adv = (tuple(x[f"adv{i}"] for i in range(4))
               if adv_bounds is not None else None)
        best, src, fg = _best_split_impl(
            x["hist"], parent_grad, parent_hess, parent_count,
            num_bins_per_feature=x["nbpf"], nan_bins=x["nanb"],
            is_categorical=x["iscat"],
            monotone=x.get("mono"),
            feature_mask=x["fmask"], cfg=cfg,
            gain_penalty=x.get("pen"),
            parent_output=parent_output,
            rand_bins=x.get("rand"),
            out_lo=out_lo, out_hi=out_hi, adv_bounds=adv,
            leaf_depth=leaf_depth,
            feature_contri=x.get("fc"),
            with_feature_gains=with_feature_gains)
        if with_feature_gains:
            return best, src, fg
        return best, src

    mapped = jax.lax.map(tile_fn, ops)
    bests, srcs = mapped[0], mapped[1]
    # Cross-block winner with the untiled argmax's exact tie-break: max
    # gain; on ties a numeric/one-hot winner beats a sorted-categorical one
    # (the untiled merge takes sorted only on STRICTLY greater gain); then
    # the lowest block (= lowest feature id, blocks are contiguous).
    gains = bests.gain
    iota = jnp.arange(nt)
    is_max = gains == jnp.max(gains)
    numeric_max = is_max & ~srcs
    first_numeric = jnp.argmin(jnp.where(numeric_max, iota, nt))
    first_any = jnp.argmin(jnp.where(is_max, iota, nt))
    ti = jnp.where(jnp.any(numeric_max), first_numeric,
                   first_any).astype(jnp.int32)
    best = jax.tree.map(lambda a: a[ti], bests)
    best = best._replace(feature=best.feature + ti * t)
    if with_feature_gains:
        return best, mapped[2].reshape(nt * t)[:f]
    return best


def _merge_sorted_categorical(best, G, H, C, parent_grad, parent_hess,
                              parent_count, parent_output, parent_gain,
                              in_feature, sorted_eligible, feature_mask,
                              penalty_col, cfg, min_count, rand_bins,
                              feature_contri=None):
    """Run the sorted many-vs-many scan and take it when it beats ``best``.
    Returns ``(best, from_sorted)``."""
    s_gain, s_mask, s_gl, s_hl, s_cl = _sorted_categorical(
        G, H, C, parent_grad, parent_hess, parent_count, parent_output,
        in_feature, cfg, min_count, rand_bins)
    # NOTE: the parent gain shift deliberately uses PLAIN lambda_l2 even
    # though the sorted children use l2+cat_l2 — the reference computes
    # gain_shift (feature_histogram.cpp:161-173) before `l2 += cat_l2`
    # (:250), and comments that this asymmetry is intentional.
    s_gain = s_gain - parent_gain
    s_gain = jnp.where(s_gain > cfg.min_gain_to_split + _EPS, s_gain, -jnp.inf)
    if penalty_col is not None:
        s_gain = s_gain - penalty_col[:, 0]
        s_gain = jnp.where(s_gain > _EPS, s_gain, -jnp.inf)
    if feature_contri is not None:
        s_scaled = s_gain * feature_contri
        s_gain = jnp.where(jnp.isfinite(s_gain) & (s_scaled > _EPS),
                           s_scaled, -jnp.inf)
    s_gain = jnp.where(sorted_eligible & feature_mask, s_gain, -jnp.inf)
    sf = jnp.argmax(s_gain).astype(jnp.int32)
    sg = s_gain[sf]
    better = sg > best.gain
    pickf = lambda a_new, a_old: jnp.where(better, a_new, a_old)
    return BestSplit(
        gain=pickf(sg, best.gain),
        feature=pickf(sf, best.feature),
        bin=pickf(jnp.asarray(0, jnp.int32), best.bin),
        default_left=pickf(jnp.asarray(False), best.default_left),
        is_cat=pickf(jnp.asarray(True), best.is_cat),
        cat_mask=jnp.where(better, s_mask[sf], best.cat_mask),
        sum_grad_left=pickf(s_gl[sf], best.sum_grad_left),
        sum_hess_left=pickf(s_hl[sf], best.sum_hess_left),
        count_left=pickf(s_cl[sf], best.count_left),
        sum_grad_right=pickf(parent_grad - s_gl[sf], best.sum_grad_right),
        sum_hess_right=pickf(parent_hess - s_hl[sf], best.sum_hess_right),
        count_right=pickf(parent_count - s_cl[sf], best.count_right),
    ), better
