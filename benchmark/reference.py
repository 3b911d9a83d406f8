"""Plain reference for histogram gradient boosting, in numpy float64.

It imports nothing of the program and takes nothing the program made except
the ANSWER under test — the trees of the timed run, as ``dump_model()``
prints them (LightGBM's public JSON) — exactly as a served model's reference
runs over the served tokens.  From the seed's raw rows and labels it works
out, independently and in float64:

- the objective's gradients and hessians at the scores those trees imply
  (``objectives/<name>.py``: LightGBM's definitions);
- each row's leaf by the trees' real-valued thresholds on the raw features
  (so binning and partition are checked together: a row binned to the wrong
  side of a threshold lands in another leaf);
- every leaf's and node's row count, gradient and hessian sums, and from
  them the leaf values and split gains LightGBM's formulas give;
- for sampled nodes the best split over every threshold the model's trees
  use (and its own quantiles for a feature they never use), i.e. how far the
  chosen split lies below the best one a plain search finds.

``round_bf16`` is the control's precision: the same sums with the gradients
and hessians rounded to bfloat16 first, which is what a one-pass MXU
histogram (the step that would tempt a later PR) would accumulate.
"""

from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------- trees


def flatten_tree(tree_info: dict) -> dict:
    """``dump_model()['tree_info'][k]`` -> flat arrays.  Children are
    encoded LightGBM's way: >= 0 an internal node, < 0 the leaf ``~c``."""
    root = tree_info["tree_structure"]
    nl = int(tree_info["num_leaves"])
    m = max(nl - 1, 0)
    t = {
        "num_leaves": nl,
        "feature": np.zeros(m, np.int64),
        "threshold": np.zeros(m, np.float64),
        "left": np.zeros(m, np.int64),
        "right": np.zeros(m, np.int64),
        "gain": np.zeros(m, np.float64),
        "internal_count": np.zeros(m, np.float64),
        "leaf_value": np.zeros(nl, np.float64),
        "leaf_count": np.zeros(nl, np.float64),
        "leaf_weight": np.zeros(nl, np.float64),
    }
    if "split_index" not in root:            # a stump: one leaf
        t["leaf_value"][0] = root["leaf_value"]
        t["leaf_count"][0] = root.get("leaf_count", 0)
        t["leaf_weight"][0] = root.get("leaf_weight", 0.0)
        return t

    def child_code(node):
        return (int(node["split_index"]) if "split_index" in node
                else ~int(node["leaf_index"]))

    stack = [root]
    while stack:
        n = stack.pop()
        if "split_index" in n:
            i = int(n["split_index"])
            if n["decision_type"] != "<=":
                raise ValueError("the reference handles numerical splits "
                                 f"only, got {n['decision_type']!r}")
            t["feature"][i] = n["split_feature"]
            t["threshold"][i] = n["threshold"]
            t["gain"][i] = n["split_gain"]
            t["internal_count"][i] = n["internal_count"]
            t["left"][i] = child_code(n["left_child"])
            t["right"][i] = child_code(n["right_child"])
            stack.append(n["left_child"])
            stack.append(n["right_child"])
        else:
            j = int(n["leaf_index"])
            t["leaf_value"][j] = n["leaf_value"]
            t["leaf_count"][j] = n["leaf_count"]
            t["leaf_weight"][j] = n["leaf_weight"]
    return t


def leaf_of_rows(X: np.ndarray, t: dict) -> np.ndarray:
    """Each row's leaf index: walk the real-valued thresholds on the raw
    feature values, all rows a level at a time."""
    n = X.shape[0]
    if t["num_leaves"] <= 1:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    active = np.arange(n)
    while active.size:
        nd = node[active]
        go_left = X[active, t["feature"][nd]].astype(np.float64) \
            <= t["threshold"][nd]
        nxt = np.where(go_left, t["left"][nd], t["right"][nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def node_sums(t: dict, leaf_sums: np.ndarray) -> np.ndarray:
    """Per-internal-node sums from per-leaf sums (``leaf_sums`` is (L, k)).
    A child is a leaf or a LATER node, so one backward sweep does it."""
    m = len(t["feature"])
    out = np.zeros((m,) + leaf_sums.shape[1:], np.float64)
    for i in range(m - 1, -1, -1):
        for c in (t["left"][i], t["right"][i]):
            out[i] += out[c] if c >= 0 else leaf_sums[~c]
    return out


def node_leaves(t: dict, i: int) -> np.ndarray:
    """Leaf indices under internal node ``i``."""
    leaves, stack = [], [i]
    while stack:
        c = stack.pop()
        if c >= 0:
            stack.extend((t["left"][c], t["right"][c]))
        else:
            leaves.append(~c)
    return np.array(sorted(leaves), np.int64)


# ------------------------------------------------------- sums and formulas


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest even), returned as float64."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def leaf_output(g: np.ndarray, h: np.ndarray, lambda_l2: float) -> np.ndarray:
    return -g / (h + lambda_l2)


def split_gain(gl, hl, gr, hr, lambda_l2: float):
    """LightGBM's gain of a split: the children's G^2/(H + l2) less the
    parent's."""
    g, h = gl + gr, hl + hr
    return (gl * gl / (hl + lambda_l2) + gr * gr / (hr + lambda_l2)
            - g * g / (h + lambda_l2))


def candidate_thresholds(flat_trees: list, X: np.ndarray, bins: int,
                         sample: np.ndarray) -> list:
    """Per feature, the sorted thresholds at which the model's own trees
    split that feature: candidates that the program's bins surely hold, so a
    sound arg-max reads about 0 against them (the reference's own quantiles
    fall between the program's bin bounds and read 1-3 %).  A feature the
    model never splits on — a scan that leaves features out never would —
    gets the reference's own: ``bins - 1`` quantiles over the row ``sample``."""
    feats = np.concatenate([t["feature"] for t in flat_trees])
    thr = np.concatenate([t["threshold"] for t in flat_trees])
    qs = np.linspace(0.0, 1.0, bins + 1)[1:-1]
    out = []
    for f in range(X.shape[1]):
        c = np.unique(thr[feats == f])
        if c.size == 0:
            c = np.unique(np.quantile(X[sample, f].astype(np.float64), qs))
        out.append(c)
    return out


def candidate_index(X: np.ndarray, cands: list) -> np.ndarray:
    """(N, F): where each value falls among its feature's candidates —
    ``x <= cands[f][j]`` exactly where the index is ``<= j``."""
    B = np.empty(X.shape, np.int32)
    for f, c in enumerate(cands):
        B[:, f] = np.searchsorted(c, X[:, f].astype(np.float64), side="left")
    return B


def best_gain_per_feature(B: np.ndarray, cands: list, rows: np.ndarray,
                          g: np.ndarray, h: np.ndarray, lambda_l2: float,
                          min_hess: float) -> np.ndarray:
    """For the node holding ``rows``: per feature the best gain over its
    candidates that leave both children ``min_hess`` (-inf where none
    does)."""
    gn, hn = g[rows], h[rows]
    G, H = gn.sum(), hn.sum()
    best = np.full(len(cands), -np.inf)
    for f, c in enumerate(cands):
        b = B[rows, f]
        gl = np.cumsum(np.bincount(b, gn, len(c) + 1))[:-1]
        hl = np.cumsum(np.bincount(b, hn, len(c) + 1))[:-1]
        ok = (hl >= min_hess) & (H - hl >= min_hess)
        if ok.any():
            with np.errstate(divide="ignore", invalid="ignore"):
                best[f] = np.max(np.where(
                    ok, split_gain(gl, hl, G - gl, H - hl, lambda_l2),
                    -np.inf))
    return best
