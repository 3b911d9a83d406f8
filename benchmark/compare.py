"""The comparison that decides ``correct`` for a training cell.

What is compared is what the timed path produced at the timed size: the
trees of the one Booster the window drove (all grown through the window's
own ``Booster.update()``), and the score vector that Booster held when the
window closed.  ``reference.py`` recomputes from the seed's raw rows, in
float64, what those trees must say.  It follows every tree's walk over every
row (the scores the next gradients are taken at), and recomputes in full the
CHECKED trees: the first ``checked_trees`` (the warm-up iteration's and the
window's first ones) and the window's last.  This module reads the gaps:

``leaves_short``     every tree of the window: how many leaves it lacks of
                     the configuration's ``num_leaves`` — a tree stopped
                     early.  Exact: the limit is 0.
``rows_misplaced``   the share of rows (summed over the checked trees) that
                     the reference's walk by the tree's thresholds puts in
                     another leaf than the program counted there: half the
                     sum of |leaf_count - reference's count| over the rows
                     — binning + partition.  Not exact: the model's
                     thresholds are float32-rounded bin bounds, so a value
                     between a bound and its rounding lands on the other
                     side (a few rows in 311 M values; PERF.md).
``leaf_value_gap``   per tree, the norm of (program's leaf values - the
                     reference's) over the norm of the reference's, each
                     leaf weighted by its rows: the relative error of the
                     step's change to the score vector — gradients, histogram
                     sums, the leaf output and the shrinkage.  Worst tree.
``split_gain_gap``   the same over a tree's splits, for the recorded gain
                     against the reference's gain of the same split, each
                     split weighted by its node's rows.  Worst tree.
``leaf_value_worst`` / ``split_gain_worst``  the single worst leaf / split,
                     over that reference value or the median one, whichever is
                     larger.  Read, printed, not held to a limit: float32
                     sibling subtraction leaves its error in the smallest
                     leaves, and so does the bfloat16 control, so the worst
                     entry separates them by 4x where the weighted norm
                     separates them far more (PERF.md).
``best_split_gap``   the split scan's arg-max, on seed-drawn nodes of the
                     checked trees (the first tree's root among them): how
                     far the chosen split's float64 gain lies below the best
                     gain over every feature and every threshold the model
                     itself uses for that feature (thresholds the program's
                     bins surely hold), as a share of that best; the norm
                     over the nodes, each weighted by its rows.
                     ``best_split_worst`` is the worst node.
``score_gap``        sampled rows: |program's final score - the sum of its
                     trees' leaf values along the reference's walk| over
                     that score or the median one, at the 99.9th percentile
                     of the rows — the score update, over every iteration of
                     the window.  Not the worst row (``score_worst``, read
                     and printed): a row on the wrong side of a
                     float32-rounded threshold (see ``rows_misplaced``) is
                     off by a whole leaf value, one row in a million.

The same readings can be taken of stand-ins for the program
(``stand_ins``): the control (sums of bfloat16-rounded gradients) and the
faults a training cell can have (half the batch left out; a state left
unchanged; one answer altered: a leaf value's sign; an arg-max that takes
the runner-up feature; a scan that leaves out the last quarter of the
features).  Benchmark runs do not take them; the control test and
``--control 1`` do.
"""

from __future__ import annotations

import numpy as np

from . import objectives
from . import reference as ref

NUMBERS = ("leaves_short", "rows_misplaced", "leaf_value_gap",
           "split_gain_gap", "best_split_gap", "score_gap",
           "leaf_value_worst", "split_gain_worst", "best_split_worst",
           "score_worst")


def _values_from_sums(t, cnt, g, h, lr, l2, bias):
    """What a tree with ``t``'s structure says when its leaves hold these
    counts and gradient / hessian sums."""
    ns = ref.node_sums(t, np.stack([cnt, g, h], axis=1))
    m = len(t["feature"])
    gain = np.zeros(m)
    for i in range(m):
        l, r = t["left"][i], t["right"][i]
        ls = ns[l] if l >= 0 else np.array([cnt[~l], g[~l], h[~l]])
        rs = ns[r] if r >= 0 else np.array([cnt[~r], g[~r], h[~r]])
        gain[i] = ref.split_gain(ls[1], ls[2], rs[1], rs[2], l2)
    with np.errstate(divide="ignore", invalid="ignore"):
        value = lr * ref.leaf_output(g, h, l2) + bias
    return {"leaf_value": value, "leaf_count": cnt, "leaf_weight": h,
            "gain": gain,
            "internal_count": ns[:, 0] if m else np.zeros(0)}


def _rel_gap(a, r, scale_of=None, quantile=None):
    """Worst entry (or the ``quantile`` of the entries) of |a - r| over
    max(|scale|, median |scale|)."""
    if len(r) == 0:
        return 0.0
    s = np.abs(r if scale_of is None else scale_of)
    den = np.maximum(s, np.median(s))
    den = np.where(den > 0, den, 1.0)
    gap = np.abs(np.asarray(a, np.float64) - r) / den
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return float(np.max(gap) if quantile is None
                 else np.quantile(gap, quantile))


def _rel_norm(a, r, weight, scale_of=None):
    """Weighted norm of a - r over the weighted norm of the scale."""
    if len(r) == 0:
        return 0.0
    s = r if scale_of is None else scale_of
    num = np.sqrt(np.sum(weight * (np.asarray(a, np.float64) - r) ** 2))
    den = np.sqrt(np.sum(weight * s ** 2))
    return float(num / den) if den > 0 else float(num)


def _tree_readings(a, r, bias):
    return {
        "rows_misplaced": float(
            np.abs(a["leaf_count"] - r["leaf_count"]).sum() / 2
            / max(r["leaf_count"].sum(), 1)),
        "leaf_value_gap": _rel_norm(a["leaf_value"], r["leaf_value"],
                                    r["leaf_count"], r["leaf_value"] - bias),
        "split_gain_gap": _rel_norm(a["gain"], r["gain"],
                                    r["internal_count"]),
        "leaf_value_worst": _rel_gap(a["leaf_value"], r["leaf_value"],
                                     r["leaf_value"] - bias),
        "split_gain_worst": _rel_gap(a["gain"], r["gain"]),
    }


def _merge(into, new):
    for k, v in new.items():
        into[k] = (into.get(k, 0) + v if k == "rows_misplaced"
                   else max(into.get(k, 0.0), v))


def _node_gaps(gaps: list) -> dict:
    """``[(rows, gap), ...]`` of the sampled nodes -> the two readings."""
    if not gaps:
        return {}
    w, gap = np.array(gaps, np.float64).T
    return {"best_split_gap": float(np.sqrt(np.sum(w * gap ** 2) / w.sum())),
            "best_split_worst": float(gap.max())}


def compare(config: dict, data: dict, trees: list, final_scores, seed: int,
            checked_trees: int, stand_ins: bool = False) -> dict:
    """Readings of the program's trees and final scores against the plain
    reference.  Returns ``{"program": {number: value}, ...}`` and, with
    ``stand_ins``, the same numbers under ``control`` and each fault."""
    params, cc = config["params"], config["correct"]
    X = data["X"]
    n, nfeat = X.shape
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    obj = objectives.load(params, data["label"], data.get("group"))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    init = obj.init_score()
    flat = [ref.flatten_tree(t) for t in trees]
    checked = set(range(min(checked_trees, len(flat)))) | {len(flat) - 1}
    sample = (np.arange(n) if n <= int(cc["score_sample_rows"]) else
              np.sort(rng.choice(n, int(cc["score_sample_rows"]),
                                 replace=False)))
    n_nodes = int(cc["sampled_nodes_per_tree"])
    if n_nodes:
        cands = ref.candidate_thresholds(flat, X, int(params["max_bin"]),
                                         sample)
        B = ref.candidate_index(X, cands)
    short_from = -(-3 * nfeat // 4)    # the short scan stops at this feature
    out = {"program": {"leaves_short": float(max(
        int(params["num_leaves"]) - t["num_leaves"] for t in flat))}}
    gaps = {"program": []}
    if stand_ins:
        out.update({"control": {}, "fault_half_batch": {},
                    "fault_state_unchanged": {}, "fault_answer_altered": {},
                    "fault_runner_up_feature": {}, "fault_scan_short": {}})
        gaps.update(fault_runner_up_feature=[], fault_scan_short=[])
    score = np.full(n, init, np.float64)
    score_prev = None                  # the scores one tree earlier

    for k, t in enumerate(flat):
        leaf = ref.leaf_of_rows(X, t)
        bias = 0.0
        if k in checked:
            g, h = obj.gradients(score)
            nl = t["num_leaves"]

            def says(rows, g_, h_, b):
                """What this tree would say had it summed these gradients
                over these rows."""
                lf = leaf[rows]
                return _values_from_sums(
                    t, np.bincount(lf, minlength=nl).astype(np.float64),
                    np.bincount(lf, g_[rows], nl),
                    np.bincount(lf, h_[rows], nl), lr, l2, b)

            every = slice(None)
            # LightGBM folds the initial score into the first tree's
            # leaves; a dump may or may not: take whichever reading fits
            bias, r = min(
                ((b, says(every, g, h, b))
                 for b in ((0.0, init) if k == 0 and init else (0.0,))),
                key=lambda br: _rel_gap(t["leaf_value"], br[1]["leaf_value"],
                                        br[1]["leaf_value"] - br[0]))
            prog = {key: t[key] for key in ("leaf_value", "leaf_count",
                                            "leaf_weight", "gain",
                                            "internal_count")}
            _merge(out["program"], _tree_readings(prog, r, bias))

            if stand_ins:
                _merge(out["control"], _tree_readings(
                    says(every, ref.round_bf16(g), ref.round_bf16(h), bias),
                    r, bias))
                # half of the batch left out
                _merge(out["fault_half_batch"], _tree_readings(
                    says(slice(0, None, 2), g, h, bias), r, bias))
                if score_prev is not None:   # grown from the state before
                    _merge(out["fault_state_unchanged"], _tree_readings(
                        says(every, *obj.gradients(score_prev), bias),
                        r, bias))
                alt = dict(prog)
                alt["leaf_value"] = prog["leaf_value"].copy()
                j = int(rng.randint(nl))
                alt["leaf_value"][j] = bias - (prog["leaf_value"][j] - bias)
                _merge(out["fault_answer_altered"],
                       _tree_readings(alt, r, bias))

            # the split scan's arg-max, on sampled nodes
            m = len(t["feature"])
            nodes = rng.choice(m, min(n_nodes, m), replace=False).tolist()
            if k == 0 and nodes and 0 not in nodes:
                nodes.append(0)
            for i in nodes:
                rows = (np.arange(n) if i == 0 else
                        np.nonzero(np.isin(leaf, ref.node_leaves(t, i)))[0])
                per_feature = ref.best_gain_per_feature(
                    B, cands, rows, g, h, l2, min_hess)
                best = float(per_feature.max())
                if not np.isfinite(best) or best <= 0:
                    continue

                def below(gain):
                    return (len(rows), max(0.0, float((best - gain) / best)))
                gaps["program"].append(below(r["gain"][i]))
                if stand_ins:
                    # an arg-max that takes the runner-up feature; a scan
                    # that never looks at the last quarter of the features
                    others = np.delete(per_feature, int(per_feature.argmax()))
                    gaps["fault_runner_up_feature"].append(
                        below(max(others.max(), 0.0)))
                    gaps["fault_scan_short"].append(
                        below(max(per_feature[:short_from].max(), 0.0)))
        score_prev = score
        score = score + (t["leaf_value"] - bias)[leaf]

    for who, node_gaps in gaps.items():
        out[who].update(_node_gaps(node_gaps))
    # the score update, over every tree the window grew
    got = np.asarray(final_scores, np.float64)[sample]
    out["program"]["score_gap"] = _rel_gap(got, score[sample], quantile=0.999)
    out["program"]["score_worst"] = _rel_gap(got, score[sample])
    if stand_ins:      # a score vector kept in bfloat16
        out["control"]["score_gap"] = _rel_gap(
            ref.round_bf16(score[sample]), score[sample], quantile=0.999)
    return out


def verdict(readings: dict, limits: dict) -> tuple:
    """``(correct, compared)``: every number of ``limits`` beside its limit,
    as ``{name: {"value": v, "limit": l}}``.  A number that is missing or
    not finite has failed."""
    compared, ok = {}, True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        compared[name] = {"value": None if v is None else float(v),
                          "limit": limit}
    return ok, compared
