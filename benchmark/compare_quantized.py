"""The comparison that decides ``correct`` for a training cell whose trees
are grown on QUANTIZED gradients (LightGBM >= 4 ``use_quantized_grad``:
``GradientDiscretizer``, Shi et al., NeurIPS 2022).

``compare.py`` sums float64 gradients into every checked tree; at a few
gradient levels the rounding noise alone would break its ``leaf_value_gap``.
This module is handed, for each checked tree, what the program says it grew
the tree on — ``(gq, hq, g_scale, h_scale)``: every row's int8 gradient and
hessian level and the two float32 scales (``GBDT.last_levels()``) — and reads
at the reference's own float64 gradients ``g``, ``h`` of the scores its walk
implies:

``quant_scale_gap``  each scale against ``max |g| / (B // 2)`` and ``max h /
                     B`` (``B`` = ``num_grad_quant_bins``; the divisors of
                     ``ops/quantize.gradient_scales`` and of the paper's
                     sec. 3.1), as a relative gap; the worse of the two,
                     worst checked tree.  A program at 2 levels, or with
                     ``B / 2 - 1`` for a divisor, reads 1.
``quant_level_gap``  the share of rows whose level is neither ``floor`` nor
                     ``ceil`` of ``x = g / scale`` (at the program's own
                     scale: the scale is the number above's); a row within
                     ``level_slack`` of an integer ``k`` — where float32 and
                     float64 may stand on either side of it — may read
                     ``k - 1``, ``k`` or ``k + 1``.  Worse channel, worst
                     checked tree.  Exact: the limit is 0.
``quant_bias_z``     stochastic rounding is unbiased: rows bucketed by the
                     fraction ``p`` of ``x`` (``bias_buckets`` buckets, the
                     rows within ``level_slack`` of an integer left out), in
                     each bucket the z-score of ``sum(level - x)`` against
                     the variance ``sum(p (1 - p))`` it has when each row is
                     rounded up with probability ``p``.  The largest |z|
                     over buckets, channels and checked trees.
                     Round-to-nearest reads hundreds.
``quant_draw_gap``   the draws are fresh every tree: for each pair of
                     checked trees and each channel, the z-score of ``sum((U_a
                     - p_a)(U_b - p_b))`` (``U`` = rounded up) against its
                     variance under independence, ``sum(p_a (1 - p_a) p_b (1
                     - p_b))``.  The largest |z|.  A key not folded by the
                     iteration draws the same uniforms every tree: hundreds.

and ``compare.py``'s six numbers with the checked trees' sums taken over
``gq * g_scale`` and ``hq * h_scale`` — what the program's int32 histograms
add exactly before one float32 rescale — so ``leaf_value_gap``,
``split_gain_gap``, ``best_split_gap`` and ``rows_misplaced`` hold to float32
tightness; ``score_gap`` still follows every row down every tree.

Departures from upstream's ``GradientDiscretizer`` that this comparison
accepts, one line each:

- one counter-based key (``jax.random``, folded by the iteration) where
  upstream keeps per-thread PRNG streams: the draws are held to being
  uniform and fresh (``quant_bias_z``, ``quant_draw_gap``), not to a stream;
- int32 histograms at every leaf where upstream picks 8, 16 or 32 bits per
  leaf by its row count: the sums are the same integers, only their width
  differs;
- the hessian is discretized even where upstream keeps a constant one
  (``is_constant_hessian``): binary log-loss has none, so no row differs;
- levels clipped to int8's +-127: out of reach at ``B`` <= 127;
- a scale floored at 1e-30 where upstream divides by zero: no cell has an
  all-zero gradient.

Stand-ins for the program (``stand_ins``; ``--control 1``): the control (the
checked trees' sums rounded to bfloat16, as a histogram kept in bfloat16
would hold them, and a score vector kept in bfloat16) and the faults a
quantized cell can have — levels at 2 levels, levels rounded to nearest,
the same draw for every tree, levels of the step before's gradients, half
the rows left out of the sums — and ``compare.py``'s runner-up feature and
short scan for ``best_split_gap``.
"""

from __future__ import annotations

import numpy as np

from . import objectives
from . import reference as ref
from .compare import (NUMBERS as _SIX, _merge, _node_gaps, _rel_gap,
                      _tree_readings, _values_from_sums, verdict)

__all__ = ["compare", "verdict", "level_readings", "draw_z", "make_levels",
           "NUMBERS"]

NUMBERS = _SIX + ("quant_scale_gap", "quant_level_gap", "quant_bias_z",
                  "quant_draw_gap")


def level_counts(num_bins: int) -> tuple:
    """The levels a scale maps the largest |g| and the largest h onto, as
    ``ops/quantize.gradient_scales`` (and the paper) has them."""
    return min(max(num_bins // 2, 1), 127), min(max(num_bins, 1), 127)


def make_levels(g, h, num_bins: int, rng, stochastic=True, uniforms=None):
    """Levels a plain discretizer makes of ``g``, ``h`` — the faults'
    stand-ins.  ``uniforms`` (2, N) replaces the fresh draw."""
    lg, lh = level_counts(num_bins)
    sg = np.float32(max(np.abs(g).max() / lg, 1e-30))
    sh = np.float32(max(np.abs(h).max() / lh, 1e-30))
    u = rng.rand(2, len(g)) if uniforms is None else uniforms
    out = []
    for x, ui in ((g / float(sg), u[0]), (h / float(sh), u[1])):
        q = np.floor(x + ui) if stochastic else np.round(x)
        out.append(np.clip(q, -127, 127).astype(np.int8))
    return out[0], out[1], sg, sh


def _channel(q, x, slack):
    """(allowed, p, up, use) of one channel: which levels are floor or ceil
    of ``x``, the fraction of ``x``, whether it was rounded up, and the
    rows that are not within ``slack`` of an integer."""
    q = np.asarray(q, np.float64)
    lo = np.floor(x)
    near = np.abs(x - np.round(x)) <= slack
    allowed = (q == lo) | (q == lo + 1) | (near & (np.abs(q - np.round(x))
                                                   <= 1))
    use = allowed & ~near
    return allowed, x - lo, np.clip(q - lo, 0, 1), use


def level_readings(levels, g, h, num_bins: int, slack: float,
                   buckets: int) -> tuple:
    """The level numbers of ONE tree, and what ``draw_z`` needs of it:
    ``({number: value}, [(p, up, use) per channel])``."""
    gq, hq, sg, sh = levels
    lg, lh = level_counts(num_bins)
    want = (np.abs(g).max() / lg, np.abs(h).max() / lh)
    out = {"quant_scale_gap": float(max(
        abs(float(s) - w) / w if w > 0 else abs(float(s))
        for s, w in zip((sg, sh), want)))}
    level_gap, z, draws = 0.0, [0.0], []
    for q, v, s in ((gq, g, sg), (hq, h, sh)):
        x = v / float(s) if float(s) > 0 else np.zeros_like(v)
        allowed, p, up, use = _channel(q, x, slack)
        level_gap = max(level_gap, float((~allowed).mean()))
        d = np.asarray(q, np.float64) - x
        which = np.minimum((p * buckets).astype(np.int64), buckets - 1)
        for b in range(buckets):
            at = use & (which == b)
            var = float(np.sum(p[at] * (1 - p[at])))
            if var > 0:
                z.append(abs(float(d[at].sum())) / np.sqrt(var))
        draws.append((p, up, use))
    out["quant_level_gap"] = level_gap
    out["quant_bias_z"] = float(max(z))
    return out, draws


def draw_z(a: list, b: list) -> float:
    """The largest |z| of two trees' rounded-up indicators' covariance
    against independence, over the channels (``level_readings``' second
    value of each)."""
    z = [0.0]
    for (pa, ua, sa), (pb, ub, sb) in zip(a, b):
        at = sa & sb
        var = float(np.sum(pa[at] * (1 - pa[at]) * pb[at] * (1 - pb[at])))
        if var > 0:
            s = float(np.sum((ua[at] - pa[at]) * (ub[at] - pb[at])))
            z.append(abs(s) / np.sqrt(var))
    return float(max(z))


def compare(config: dict, data: dict, trees: list, final_scores, seed: int,
            checked: list, levels: dict, stand_ins: bool = False) -> dict:
    """Readings of the program's trees, levels and final scores against the
    plain reference.  ``checked`` lists the trees recomputed in full;
    ``levels[k]`` is tree ``k``'s ``(gq, hq, g_scale, h_scale)`` for each of
    them.  Returns ``{"program": {number: value}, ...}`` and, with
    ``stand_ins``, the same numbers under ``control`` and each fault."""
    params, cc = config["params"], config["correct"]
    X = data["X"]
    n, nfeat = X.shape
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    nbins = int(params.get("num_grad_quant_bins", 4))
    slack, buckets = float(cc["level_slack"]), int(cc["bias_buckets"])
    obj = objectives.load(params, data["label"], data.get("group"))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    init = obj.init_score()
    flat = [ref.flatten_tree(t) for t in trees]
    checked = sorted(k for k in set(checked) if 0 <= k < len(flat))
    score_rows = (np.arange(n) if n <= int(cc["score_sample_rows"]) else
                  np.sort(rng.choice(n, int(cc["score_sample_rows"]),
                                     replace=False)))
    n_nodes = int(cc["sampled_nodes_per_tree"])
    if n_nodes:
        cands = ref.candidate_thresholds(flat, X, int(params["max_bin"]),
                                         score_rows)
        B = ref.candidate_index(X, cands)
    short_from = -(-3 * nfeat // 4)     # the short scan stops at this feature
    out = {"program": {"leaves_short": float(max(
        int(params["num_leaves"]) - t["num_leaves"] for t in flat))}}
    gaps = {"program": []}
    level_faults = ("fault_two_levels", "fault_round_nearest",
                    "fault_same_draw", "fault_state_unchanged")
    if stand_ins:
        out.update({"control": {}, "fault_half_batch": {},
                    "fault_runner_up_feature": {}, "fault_scan_short": {}},
                   **{f: {} for f in level_faults})
        gaps.update(fault_runner_up_feature=[], fault_scan_short=[])
        same_u = rng.rand(2, n)            # the one draw of "same draw"
    draws = {who: [] for who in ("program",) + level_faults}
    score = np.full(n, init, np.float64)
    score_prev = None

    for k, t in enumerate(flat):
        leaf = ref.leaf_of_rows(X, t)
        bias = 0.0
        if k in checked:
            g, h = obj.gradients(score)
            nl = t["num_leaves"]
            gq, hq, sg, sh = levels[k]

            def read_levels(who, lv):
                got, d = level_readings(lv, g, h, nbins, slack, buckets)
                _merge(out[who], got)
                for earlier in draws[who]:
                    _merge(out[who], {"quant_draw_gap": draw_z(earlier, d)})
                _merge(out[who], {"quant_draw_gap": 0.0})
                draws[who].append(d)

            read_levels("program", levels[k])
            # what the int32 histograms add: the levels times the scales
            gd = np.asarray(gq, np.float64) * float(sg)
            hd = np.asarray(hq, np.float64) * float(sh)

            def sums(rows, g_, h_):
                lf = leaf[rows]
                return (np.bincount(lf, minlength=nl).astype(np.float64),
                        np.bincount(lf, g_[rows], nl),
                        np.bincount(lf, h_[rows], nl))

            every = slice(None)
            cnt, G, H = sums(every, gd, hd)
            bias, r = min(
                ((b, _values_from_sums(t, cnt, G, H, lr, l2, b))
                 for b in ((0.0, init) if k == 0 and init else (0.0,))),
                key=lambda br: _rel_gap(t["leaf_value"], br[1]["leaf_value"],
                                        br[1]["leaf_value"] - br[0]))
            prog = {key: t[key] for key in ("leaf_value", "leaf_count",
                                            "leaf_weight", "gain",
                                            "internal_count")}
            _merge(out["program"], _tree_readings(prog, r, bias))

            if stand_ins:
                _merge(out["control"], _tree_readings(_values_from_sums(
                    t, cnt, ref.round_bf16(G), ref.round_bf16(H), lr, l2,
                    bias), r, bias))
                c2, G2, H2 = sums(slice(0, None, 2), gd, hd)
                _merge(out["fault_half_batch"], _tree_readings(
                    _values_from_sums(t, c2, G2, H2, lr, l2, bias), r, bias))
                read_levels("fault_two_levels",
                            make_levels(g, h, 2, rng))
                read_levels("fault_round_nearest",
                            make_levels(g, h, nbins, rng, stochastic=False))
                read_levels("fault_same_draw",
                            make_levels(g, h, nbins, rng, uniforms=same_u))
                if score_prev is not None:
                    read_levels("fault_state_unchanged", make_levels(
                        *obj.gradients(score_prev), nbins, rng))

            # the split scan's arg-max, on sampled nodes, over the sums the
            # program's histograms hold
            m = len(t["feature"])
            nodes = rng.choice(m, min(n_nodes, m), replace=False).tolist()
            if k == 0 and nodes and 0 not in nodes:
                nodes.append(0)
            for i in nodes:
                rows = (np.arange(n) if i == 0 else
                        np.nonzero(np.isin(leaf, ref.node_leaves(t, i)))[0])
                per_feature = ref.best_gain_per_feature(
                    B, cands, rows, gd, hd, l2, min_hess)
                best = float(per_feature.max())
                if not np.isfinite(best) or best <= 0:
                    continue

                def below(gain):
                    return (len(rows), max(0.0, float((best - gain) / best)))
                gaps["program"].append(below(r["gain"][i]))
                if stand_ins:
                    others = np.delete(per_feature, int(per_feature.argmax()))
                    gaps["fault_runner_up_feature"].append(
                        below(max(others.max(), 0.0)))
                    gaps["fault_scan_short"].append(
                        below(max(per_feature[:short_from].max(), 0.0)))
        score_prev = score
        score = score + (t["leaf_value"] - bias)[leaf]

    for who, node_gaps in gaps.items():
        out[who].update(_node_gaps(node_gaps))
    got = np.asarray(final_scores, np.float64)[score_rows]
    want = score[score_rows]
    out["program"]["score_gap"] = _rel_gap(got, want, quantile=0.999)
    out["program"]["score_worst"] = _rel_gap(got, want)
    if stand_ins:        # a score vector kept in bfloat16
        out["control"]["score_gap"] = _rel_gap(
            ref.round_bf16(want), want, quantile=0.999)
    return out
