"""The comparison that decides ``correct`` for a training cell whose trees
are grown on a row SAMPLE (GOSS: LightGBM ``goss.hpp``).

``compare.py`` sums every row into every checked tree, so on a sampled tree
its numbers would read the sampling itself.  This module is handed, for each
checked tree, the sample the program says it grew the tree on — ``None``
(every row at weight 1) or ``(rows, weights)``: the in-bag row ids and each
one's multiplier of its gradient and hessian — and reads

- the six numbers of ``compare.py`` with the CHECKED trees' sums taken over
  the in-bag rows with their weights (a leaf's count is its in-bag rows, as
  the program's dump counts them); ``score_gap`` still follows EVERY row's
  walk down EVERY tree, which is what holds the out-of-bag rows' way to
  their leaf to the reference;
- ``leaves_short`` read so that a tree which RAN OUT of splits is not taken
  for one stopped early.  A sampled cell's window holds three times the
  trees of the unsampled one, and past the hundredth the ranking hessians
  have shrunk so far that a tree cannot hold ``num_leaves`` leaves of
  ``min_sum_hessian_in_leaf`` each (the chip's runs: every tree to the
  111th has 255 leaves, the 125th lacks 7-16).  A leaf can be split only if
  its hessian sum holds two children of ``min_sum_hessian_in_leaf``; so the
  leaves a tree lacks count only where its heaviest leaf still had
  ``SPLIT_ROOM`` times that (2.2: within a tenth of the bare 2 a leaf may
  find no threshold that balances) — by the reference's own weighted sums
  in the checked trees, by the dump's ``leaf_weight`` in the others.  The
  configurations this comparison serves set no other stop
  (``min_data_in_leaf`` 0, no ``max_depth``, ``min_gain_to_split`` 0).
  ``leaves_lacking`` (the raw count) and ``heaviest_unsplit`` (that leaf's
  sum over ``min_sum_hessian_in_leaf``) are read and printed, not held;
- three numbers of the sample itself, at the reference's own float64
  gradients and hessians ``g``, ``h`` of the scores its walk implies:

``sample_size_gap``  a tree before ``int(1 / learning_rate)`` holds every row
                     at weight 1; a later one exactly ``top_k = int(N *
                     top_rate)`` rows of weight 1 and ``other_k = int(N *
                     other_rate)`` rows of weight ``(N - top_k) / other_k``
                     (float32), no row twice, none out of range.  The rows
                     that break this, as a share of the rows the tree should
                     hold; worst checked tree.  Exact: the limit is 0.
``sample_top_gap``   the share of a tree's weight-1 rows whose ``|g * h|``
                     lies below the ``top_k``-th largest of all rows by more
                     than ``top_allowance`` of it (the program ranks float32
                     products at float32 scores: rows within that of the
                     threshold may swap).  Worst sampled tree.
                     ``sample_top_exact`` is the same share at allowance 0:
                     read, printed, not held to a limit.
``sample_draw_gap``  the drawn rows are uniform over the rest and fresh each
                     iteration, as z-scores: of the drawn rows' mean rank by
                     ``|g * h|`` among the rest (uniform: 1/2, variance
                     1/12 over ``other_k`` with the finite-population
                     factor), and of the overlap of two checked trees'
                     draws against its hypergeometric mean (the rows of the
                     earlier draw still outside the later top set, times
                     ``other_k / (N - top_k)``).  The largest |z|.

Departures from ``goss.hpp``, which this comparison accepts.  LightGBM's
``BaggingHelper`` works per thread block: each block takes its own
``top_k`` by ``ArgMaxAtK`` and streams through its remaining rows keeping
each with probability ``(rest_need) / (rest_all)`` — a sequential draw whose
size is exact per block and whose sets depend on the thread count.  Here
the top set is the global one (what one block gives), and the draw is any
uniform ``other_k``-subset of the rest: the z-scores test uniformity and
freshness, not LightGBM's particular stream.  Ties at the threshold: any of
the tied rows may stand in the top set (``sample_top_gap`` counts only rows
BELOW the threshold).  The amplification is LightGBM's ``(cnt - top_k) /
other_k``.

Stand-ins for the program (``stand_ins``; ``--control 1``): the control (sums
of bfloat16-rounded gradients, a top set ranked by bfloat16 products, scores
kept in bfloat16) and the faults a
sampled cell can have — no amplification; the drawn rows left out of the
sums; the "drawn" set taken as the next-largest rows; the same draw every
iteration; out-of-bag rows' scores not updated; sampling from iteration 0 —
and, to anchor ``best_split_gap``, ``compare.py``'s runner-up feature and
short scan.
"""

from __future__ import annotations

import numpy as np

from . import objectives
from . import reference as ref
from .compare import (_merge, _node_gaps, _rel_gap, _tree_readings,
                      _values_from_sums, verdict)

__all__ = ["compare", "verdict", "goss_sizes", "sample_readings",
           "short_tree"]

EVERY = None          # a tree's sample: every row at weight 1
SPLIT_ROOM = 2.2      # x min_sum_hessian_in_leaf: a leaf that can be split


def short_tree(num_leaves: int, leaf_weight, want: int,
               min_hess: float) -> dict:
    """``leaves_short`` and its two diagnostics for ONE tree: the leaves it
    lacks of ``want``, held against it only where its heaviest leaf could
    still have been split."""
    lacking = max(int(want) - int(num_leaves), 0)
    if not lacking:
        return {"leaves_short": 0.0, "leaves_lacking": 0.0,
                "heaviest_unsplit": 0.0}
    heaviest = float(np.max(leaf_weight)) / min_hess
    return {"leaves_short": float(lacking if heaviest >= SPLIT_ROOM else 0),
            "leaves_lacking": float(lacking), "heaviest_unsplit": heaviest}


def goss_sizes(n: int, params: dict) -> tuple:
    """``(unsampled_iters, top_k, other_k, amplify)`` as ``goss.hpp`` has
    them; ``amplify`` is the float32 the rows' weights must equal."""
    top_k = max(int(n * float(params["top_rate"])), 1)
    other_k = int(n * float(params["other_rate"]))
    amplify = np.float32((n - top_k) / other_k) if other_k else np.float32(0)
    return (int(1.0 / float(params.get("learning_rate", 0.1))), top_k,
            other_k, amplify)


def _split_sample(sample, amplify):
    """``(top rows, drawn rows, other rows)`` of a sample by weight."""
    rows, w = (np.asarray(a) for a in sample)
    w = w.astype(np.float32)
    is_top, is_drawn = w == np.float32(1), w == amplify
    if amplify == np.float32(1):
        is_drawn = np.zeros(len(w), bool)
    return rows[is_top], rows[is_drawn & ~is_top], rows[~is_top & ~is_drawn]


def sample_readings(sample, n: int, sampled: bool, sizes: tuple,
                    s: np.ndarray, top_allowance: float,
                    earlier_draw=None) -> dict:
    """The three sampling numbers (and ``sample_top_exact``) of ONE tree's
    sample.  ``s`` is the reference's ``|g * h|`` of every row;
    ``earlier_draw`` the drawn rows of an earlier checked sampled tree."""
    _, top_k, other_k, amplify = sizes
    if not sampled:
        if sample is EVERY:
            return {"sample_size_gap": 0.0}
        rows, w = (np.asarray(a) for a in sample)
        good = np.zeros(n, bool)
        ok = (rows >= 0) & (rows < n) & (w.astype(np.float32) == 1)
        good[rows[ok]] = True
        return {"sample_size_gap": float(
            (n - good.sum() + (len(rows) - good.sum())) / n)}
    if sample is EVERY:               # every row, where a sample is due
        return {"sample_size_gap": float(
            (n - top_k - other_k) / (top_k + other_k))}
    rows = np.asarray(sample[0])
    top, drawn, other = _split_sample(sample, amplify)
    bad = (abs(len(top) - top_k) + abs(len(drawn) - other_k) + len(other)
           + (len(rows) - len(np.unique(rows)))
           + int(((rows < 0) | (rows >= n)).sum()))
    out = {"sample_size_gap": float(bad / (top_k + other_k))}
    top, drawn = top[(top >= 0) & (top < n)], drawn[(drawn >= 0) & (drawn < n)]
    # ---- the top set, against the top_k-th largest of all rows
    kth = np.partition(s, n - top_k)[n - top_k]
    out["sample_top_gap"] = float(
        (s[top] < kth * (1.0 - top_allowance)).mean()) if len(top) else 1.0
    out["sample_top_exact"] = float((s[top] < kth).mean()) if len(top) else 1.0
    # ---- the draw: uniform over the rest ...
    rest = np.ones(n, bool)
    rest[top] = False
    R = int(rest.sum())
    z = [0.0]
    if len(drawn) and R > len(drawn):
        rank = np.empty(n, np.float64)
        rest_ids = np.flatnonzero(rest)
        rank[rest_ids[np.argsort(s[rest_ids], kind="stable")]] = (
            np.arange(R) + 0.5) / R
        inside = drawn[rest[drawn]]
        var = (1.0 / 12.0) / len(drawn) * (R - len(drawn)) / max(R - 1, 1)
        z.append(abs(rank[inside].mean() - 0.5) / np.sqrt(var)
                 if len(inside) else np.inf)
        # ... and fresh: its overlap with an earlier tree's draw
        if earlier_draw is not None and len(earlier_draw):
            marked = int(rest[earlier_draw].sum())
            p = marked / R
            mean = len(drawn) * p
            var = len(drawn) * p * (1 - p) * (R - len(drawn)) / max(R - 1, 1)
            both = len(np.intersect1d(drawn, earlier_draw))
            z.append(abs(both - mean) / np.sqrt(var) if var > 0 else
                     (0.0 if both == mean else np.inf))
    out["sample_draw_gap"] = float(max(z))
    return out


def _goss_stand_in(s, rng, sizes, drawn=None, next_largest=False):
    """A sample built from ``s`` (the reference's own ``|g * h|``, or the
    control's): its exact top set and ``drawn`` (default: a uniform draw of
    the rest; with ``next_largest`` the next ``other_k`` rows by ``s``)."""
    _, top_k, other_k, amplify = sizes
    order = np.argsort(-np.abs(s), kind="stable")
    top = order[:top_k]
    if next_largest:
        drawn = order[top_k:top_k + other_k]
    elif drawn is None:
        drawn = rng.choice(order[top_k:], other_k, replace=False)
    else:
        drawn = np.setdiff1d(drawn, top)
    rows = np.concatenate([top, drawn])
    w = np.concatenate([np.ones(len(top), np.float32),
                        np.full(len(drawn), amplify, np.float32)])
    o = np.argsort(rows, kind="stable")
    return rows[o], w[o]


def compare(config: dict, data: dict, trees: list, final_scores, seed: int,
            checked: list, samples: dict, stand_ins: bool = False) -> dict:
    """Readings of the program's trees, samples and final scores against
    the plain reference.  ``checked`` lists the trees recomputed in full;
    ``samples[k]`` is tree ``k``'s sample for each of them.  Returns
    ``{"program": {number: value}, ...}`` and, with ``stand_ins``, the same
    numbers under ``control`` and each fault."""
    params, cc = config["params"], config["correct"]
    X = data["X"]
    n, nfeat = X.shape
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    sizes = goss_sizes(n, params)
    unsampled_iters, top_k, other_k, amplify = sizes
    allowance = float(cc["top_allowance"])
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
    short_from = -(-3 * nfeat // 4)
    want = int(params["num_leaves"])
    out = {"program": {}}
    for k, t in enumerate(flat):
        if k not in checked:       # the checked ones: by the reference's sums
            _merge(out["program"], short_tree(
                t["num_leaves"], t["leaf_weight"], want, min_hess))
    gaps = {"program": []}
    faults = ("fault_no_amplify", "fault_drawn_left_out",
              "fault_next_largest", "fault_same_draw",
              "fault_oob_not_updated", "fault_sampled_from_0",
              "fault_runner_up_feature", "fault_scan_short")
    if stand_ins:
        out.update({"control": {}}, **{f: {} for f in faults})
        gaps.update(fault_runner_up_feature=[], fault_scan_short=[])
    score = np.full(n, init, np.float64)
    oob_left = np.zeros(n, np.float64)   # what out-of-bag rows would lack
    earlier_draw = stand_in_draw = None

    for k, t in enumerate(flat):
        leaf = ref.leaf_of_rows(X, t)
        bias = 0.0
        if k in checked:
            g, h = obj.gradients(score)
            s = np.abs(g * h)
            nl = t["num_leaves"]
            sampled = k >= unsampled_iters
            sample = samples[k]
            _merge(out["program"], sample_readings(
                sample, n, sampled, sizes, s, allowance, earlier_draw))
            if sample is EVERY:
                rows, w = np.arange(n), np.ones(n)
            else:
                rows, w = (np.asarray(a) for a in sample)
                keep = (rows >= 0) & (rows < n)
                rows, w = rows[keep], w[keep].astype(np.float64)

            def says(rows_, w_, g_, h_, b):
                """What this tree would say had it summed these gradients
                over these rows at these weights."""
                lf = leaf[rows_]
                return _values_from_sums(
                    t, np.bincount(lf, minlength=nl).astype(np.float64),
                    np.bincount(lf, g_[rows_] * w_, nl),
                    np.bincount(lf, h_[rows_] * w_, nl), lr, l2, b)

            bias, r = min(
                ((b, says(rows, w, g, h, b))
                 for b in ((0.0, init) if k == 0 and init else (0.0,))),
                key=lambda br: _rel_gap(t["leaf_value"], br[1]["leaf_value"],
                                        br[1]["leaf_value"] - br[0]))
            prog = {key: t[key] for key in ("leaf_value", "leaf_count",
                                            "leaf_weight", "gain",
                                            "internal_count")}
            _merge(out["program"], _tree_readings(prog, r, bias))
            _merge(out["program"], short_tree(nl, r["leaf_weight"], want,
                                              min_hess))

            if stand_ins:
                _merge(out["control"], _tree_readings(
                    says(rows, w, ref.round_bf16(g), ref.round_bf16(h),
                         bias), r, bias))
                if sampled and sample is not EVERY:
                    # a top set ranked by bfloat16 products
                    _merge(out["control"], sample_readings(
                        _goss_stand_in(ref.round_bf16(g) * ref.round_bf16(h),
                                       rng, sizes), n, True, sizes, s,
                        allowance))
                    is_top = w == 1.0
                    _merge(out["fault_no_amplify"], _tree_readings(
                        says(rows, np.ones(len(rows)), g, h, bias), r, bias))
                    _merge(out["fault_drawn_left_out"], _tree_readings(
                        says(rows[is_top], w[is_top], g, h, bias), r, bias))
                    _merge(out["fault_next_largest"], sample_readings(
                        _goss_stand_in(s, rng, sizes, next_largest=True),
                        n, True, sizes, s, allowance))
                    mine = _goss_stand_in(s, rng, sizes, drawn=stand_in_draw)
                    _merge(out["fault_same_draw"], sample_readings(
                        mine, n, True, sizes, s, allowance, stand_in_draw))
                    if stand_in_draw is None:
                        stand_in_draw = _split_sample(mine, amplify)[1]
                    out_of_bag = np.ones(n, bool)
                    out_of_bag[rows] = False
                    oob_left += np.where(
                        out_of_bag, (t["leaf_value"] - bias)[leaf], 0.0)
                if not sampled:
                    _merge(out["fault_sampled_from_0"], sample_readings(
                        _goss_stand_in(s, rng, sizes), n, False, sizes, s,
                        allowance))
            if sampled and sample is not EVERY:
                earlier_draw = _split_sample(sample, amplify)[1]

            # the split scan's arg-max, on sampled nodes, over the in-bag
            # rows at their weights
            gw, hw = np.zeros(n), np.zeros(n)
            gw[rows], hw[rows] = g[rows] * w, h[rows] * w
            m = len(t["feature"])
            nodes = rng.choice(m, min(n_nodes, m), replace=False).tolist()
            if k == 0 and nodes and 0 not in nodes:
                nodes.append(0)
            for i in nodes:
                at = (rows if i == 0 else
                      rows[np.isin(leaf[rows], ref.node_leaves(t, i))])
                per_feature = ref.best_gain_per_feature(
                    B, cands, at, gw, hw, l2, min_hess)
                best = float(per_feature.max())
                if not np.isfinite(best) or best <= 0:
                    continue

                def below(gain):
                    return (len(at), max(0.0, float((best - gain) / best)))
                gaps["program"].append(below(r["gain"][i]))
                if stand_ins:
                    others = np.delete(per_feature, int(per_feature.argmax()))
                    gaps["fault_runner_up_feature"].append(
                        below(max(others.max(), 0.0)))
                    gaps["fault_scan_short"].append(
                        below(max(per_feature[:short_from].max(), 0.0)))
        score = score + (t["leaf_value"] - bias)[leaf]

    for who, node_gaps in gaps.items():
        out[who].update(_node_gaps(node_gaps))
    # the score update, over every tree and EVERY row — the sampled trees'
    # out-of-bag rows among them
    got = np.asarray(final_scores, np.float64)[score_rows]
    want = score[score_rows]
    out["program"]["score_gap"] = _rel_gap(got, want, quantile=0.999)
    out["program"]["score_worst"] = _rel_gap(got, want)
    if stand_ins:
        out["control"]["score_gap"] = _rel_gap(
            ref.round_bf16(want), want, quantile=0.999)
        out["fault_oob_not_updated"]["score_gap"] = _rel_gap(
            (score - oob_left)[score_rows], want, quantile=0.999)
    return out
